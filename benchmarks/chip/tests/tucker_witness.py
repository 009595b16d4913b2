"""A second witness for the Tucker factor update at the nell-2 cell's size.
Runs on a TPU only:

    python3 benchmarks/chip/tests/tucker_witness.py --seed 3915100001 --jobs 6

For each job it runs job `job` of a run seeded `seed` as run.py's window
does (the program's Tucker workspace, `decompose` at the cell's size),
replays it one iteration short, and rebuilds each mode's TTMc unfolding Y in
float64 with the reference, teacher-forced as check.py does.  It prints one
JSON line per job, per mode: the share of Y's best rank-R energy that a span
misses (`reference.tucker.energy_gap`, check.py's `update_gap`) for the
program's factor and for the program's own update formula
(`repro.tucker.hooi._factor_from_unfolding`: the float32 Gram of Y and its
`eigh`) applied to float32 Y on the TPU and on the host CPU, and the spectral
gap (s_R^2 - s_{R+1}^2) / s_1^2.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent.parent / "src"))

import run  # noqa: E402
from readings import seed_list  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--jobs", type=seed_list, required=True)
    ap.add_argument("--config", default="frostt-nell2-2m")
    ap.add_argument("--traffic", default="tucker-r8")
    a = ap.parse_args()

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("tucker_witness.py: no TPU; nothing run")
    run.enable_compile_cache(run.ROOT)
    from repro.api import decompose
    from repro.core.coo import SparseTensor
    from repro.tucker.hooi import _factor_from_unfolding

    import check
    import tensors
    from reference import numerics
    from reference import tucker as ref

    config = run.load_json(run.HERE / "configs" / f"{a.config}.json")
    traffic = run.load_json(run.HERE / "traffic" / f"{a.traffic}.json")
    rank, iters = tuple(traffic["rank"]), int(traffic["iters_per_job"])
    job = dict(format=traffic["format"], method="pallas", tol=traffic["tol"])
    idx, vals, shape = tensors.generate(config, a.seed)
    st = SparseTensor(idx, vals, shape)
    ws = run.load_callable(traffic["workspace"])(st, rank)
    cpu = jax.devices("cpu")[0]

    def formula(y32, r, device):
        with jax.default_matmul_precision("highest"):
            return np.asarray(_factor_from_unfolding(jax.device_put(y32, device), r))

    for j in a.jobs:
        s = run.job_seed(a.seed, j)
        done = decompose(st, rank, planned=ws, seed=s, iters=iters, **job)
        replay = decompose(st, rank, planned=ws, seed=s, iters=iters - 1, **job)
        before, after = check.state_arrays(replay), check.state_arrays(done)
        ar = numerics.exact()
        with ar.scope():
            updates, _, _ = ref.iteration(ar, check.tensor(ar, idx, vals), before, forced=after)
            modes = [{"y": np.asarray(u["unfolding"]), "energy": float(u["energy"])}
                     for u in updates[:-1]]
        out = []
        for m, (mine, u) in enumerate(zip(after["factors"], modes)):
            r = rank[m]
            y, y32 = u["y"], u["y"].astype(np.float32)
            sv = np.linalg.svd(y, compute_uv=False)
            out.append({
                "mode": m,
                "program": ref.energy_gap(mine, y, u["energy"]),
                "formula_tpu": ref.energy_gap(formula(y32, r, jax.devices()[0]), y, u["energy"]),
                "formula_cpu": ref.energy_gap(formula(y32, r, cpu), y, u["energy"]),
                "spectral_gap": float((sv[r - 1] ** 2 - sv[r] ** 2) / sv[0] ** 2),
            })
        print(json.dumps({"seed": a.seed, "job": j, "job_seed": s, "modes": out}), flush=True)


if __name__ == "__main__":
    main()
