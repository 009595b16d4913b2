"""The kernels' share of their roofline, in percent: the least time the
chip needs for the kernels' compulsory work (costs/<format>.py; per mode
the larger of bytes over peak bandwidth and operations over peak rate,
peaks.json) over the kernels' device time, both per iteration."""


def read(r):
    if r.trace is None or not r.trace.kernel_events or not r.iterations:
        return None
    return 100.0 * r.bound_s * r.iterations / r.trace.kernel_s
