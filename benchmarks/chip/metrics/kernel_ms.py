"""Device milliseconds of the Pallas kernels per ALS iteration: the summed
durations of the kernel events in the traced window over the iterations
completed in it."""


def read(r):
    if r.trace is None or not r.trace.kernel_events or not r.iterations:
        return None
    return 1e3 * r.trace.kernel_s / r.iterations
