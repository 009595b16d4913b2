"""GiB per ALS iteration that the Pallas kernels' block specs move from HBM:
the program's `kernel.fetch_bytes{mode=}` gauges, summed over modes (each
mode's kernel runs once per iteration).  The workspace counts them once
from its plans when run.py builds it: every stream block, an input-factor
tile at each tile-id change, the accumulator tile read and written at each
change, every tile afresh at the first step of each SMEM chunk, lanes as
each kernel pads them.  A program that records no such gauge reads
nothing."""


def read(r):
    from repro.obs import metrics

    gauges = metrics.snapshot()["gauges"]
    total = sum(v for k, v in gauges.items() if k.startswith("kernel.fetch_bytes{"))
    return total / 2**30 if total else None
