"""Device milliseconds per ALS iteration outside the Pallas kernels: the
sweep's grams, solves and eigh, its fit pass over the nonzeros, and each
job's init and unpad, from the traced window's busy time less kernel
time."""


def read(r):
    if r.trace is None or not r.trace.kernel_events or not r.iterations:
        return None
    return 1e3 * (r.trace.busy_s - r.trace.kernel_s) / r.iterations
