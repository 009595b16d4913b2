"""Peak device memory of the run in GiB: the device's own
`peak_bytes_in_use`, read after the window (set-up's layouts and the
window's jobs both count)."""


def read(r):
    return r.hbm_peak_bytes / 2**30 if r.hbm_peak_bytes else None
