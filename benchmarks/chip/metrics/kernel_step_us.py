"""Device microseconds per Pallas kernel grid step: the kernels' device
time per ALS iteration (as `kernel_ms` reads it) over the program's
`kernel.grid_steps{mode=}` gauges summed over modes (each mode's kernel
runs its plan's blocks once per iteration).  The cost of one step, apart
from how many steps the layout makes.  A program that records no such
gauge reads nothing."""


def read(r):
    from repro.obs import metrics

    if r.trace is None or not r.trace.kernel_events or not r.iterations:
        return None
    gauges = metrics.snapshot()["gauges"]
    steps = sum(v for k, v in gauges.items() if k.startswith("kernel.grid_steps{"))
    return 1e6 * r.trace.kernel_s / r.iterations / steps if steps else None
