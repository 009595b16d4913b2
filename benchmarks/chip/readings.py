"""The readings that a cell's limits are set from (limits/<workload>.json).

    python3 benchmarks/chip/readings.py --workload nell2.cp.steady \
        --seeds 1-12 --control-seeds 1-3

Runs on a TPU only, all seeds in one process.  For each seed it builds the
cell's tensor and workspace as run.py does, runs one job through the timed
path (`decompose` at the cell's size), replays it one iteration short and
prints one JSON line: the program's gaps against the float64 reference (the
lower readings) and, for the control seeds, the gaps of the control, the
reference computed in the program's place one precision step down (the
upper readings).  The benchmark's own runs never run the control.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent.parent / "src"))

import run  # noqa: E402


def seed_list(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    a = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("readings.py: no TPU; nothing run")
    run.enable_compile_cache(run.ROOT)
    from repro.api import decompose
    from repro.core.coo import SparseTensor

    import check
    import tensors

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    config = run.load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = run.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    fmt, iters = traffic["format"], int(traffic["iters_per_job"])
    rank = traffic["rank"] if isinstance(traffic["rank"], int) else tuple(traffic["rank"])
    method, place = run.placement(traffic, cell["chips"])
    job = dict(format=fmt, method=method, tol=traffic["tol"], **place, **traffic.get("options", {}))
    for seed in a.seeds:
        t0 = time.perf_counter()
        idx, vals, shape = tensors.generate(config, seed)
        st = SparseTensor(idx, vals, shape)
        ws = run.load_callable(traffic["workspace"])(st, rank, **place)
        s = run.job_seed(seed, 0)
        done = decompose(st, rank, planned=ws, seed=s, iters=iters, **job)
        replay = decompose(st, rank, planned=ws, seed=s, iters=iters - 1, **job)
        replay_gap = max(abs(x - y) for x, y in zip(replay.fit_history, done.fit_history))
        before, after = check.state_arrays(replay), check.state_arrays(done)
        fit = float(done.fit_history[-1])
        del ws, done, replay
        gc.collect()
        rec = {"workload": a.workload, "seed": seed, "fits": None, "replay_gap": replay_gap,
               "program": check.program_gaps(fmt, idx, vals, before, after, fit)}
        if seed in a.control_seeds:
            rec["control"] = check.control_gaps(fmt, idx, vals, before)
        rec["seconds"] = time.perf_counter() - t0
        rec["fits"] = fit
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
