"""The harness on the CPU at test sizes: tiny cells over the data under
tests/data, run through run.run with the chip check skipped.  `CELLS` run
on one device; `SHARDED` on four, which a process has only where
XLA_FLAGS forces four host devices (sharded_cell.py)."""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(CHIP.parent.parent / "src"))

import run  # noqa: E402

DATA = HERE / "data"
CELLS = {
    "tiny.cp": ("tiny", "cp-r4"),
    "tiny4.cp": ("tiny4", "cp-r4"),
    "tiny.tt": ("tiny", "tt-r3"),
    "tiny.tucker": ("tiny", "tucker-r3"),
}
SHARDED = {"tiny4.cp.sharded": ("tiny4", "cp-r4-sharded", 4)}


def bench() -> dict:
    b = json.loads((CHIP.parent.parent / "BENCHMARK.json").read_text())
    cells = {**{n: (c, t, 1) for n, (c, t) in CELLS.items()}, **SHARDED}
    b["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": chips, "why": "test"}
                      for n, (c, t, chips) in cells.items()]
    return b


def run_cell(name: str, seed: int = 5, seconds: float = 0.2, trace: bool = False) -> dict:
    return run.run(bench(), name, seed, seconds, trace, t_start=time.perf_counter(),
                   data_dir=DATA, log=lambda msg: print(msg, file=sys.stderr))
