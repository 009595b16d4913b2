"""Record the small chip trace with the program's sweep scopes that
tests/test_scope_split.py reads.

    python3 benchmarks/chip/tests/record_scoped_trace.py --out <dir>

Runs on a TPU only.  As record_trace.py: two CP jobs of two iterations each
on its small tensor, inside the benchmark's `bench.window` / `bench.job`
annotations, with the program's tracer on.  Writes `<dir>/scoped.xplane.pb`
(the checkout's path replaced by a placeholder of the same length) and
`<dir>/scoped.scopes.json`, the workspace's `sweep_scopes()`: for each
compiled sweep program, its module name, fingerprint and
{instruction name: scope}.
"""
import argparse
import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import tensors  # noqa: E402
import trace_reduce  # noqa: E402
from record_trace import CONFIG  # noqa: E402

RANK, JOBS, ITERS = 16, (1, 2), 2


def record(out: Path) -> None:
    from repro.api import decompose
    from repro.core.coo import SparseTensor
    from repro.kernels.ops import make_planned_cp_als
    from repro.obs import trace as program_trace

    idx, vals, shape = tensors.generate(CONFIG, 1)
    st = SparseTensor(idx, vals, shape)
    ws = make_planned_cp_als(st, RANK)
    decompose(st, RANK, planned=ws, iters=ITERS, seed=0)  # compile outside the trace
    norm_x_sq = jnp.asarray(float(np.sum(vals.astype(np.float64) ** 2)), jnp.float32)
    scopes = ws.sweep_scopes(jnp.asarray(idx), jnp.asarray(vals), norm_x_sq)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        program_trace.enable()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for seed in JOBS:
                with jax.profiler.TraceAnnotation("bench.job"):
                    state = decompose(st, RANK, planned=ws, iters=ITERS, seed=seed)
                    jax.block_until_ready(state.factors)
        program_trace.disable()
        jax.profiler.stop_trace()
        raw = Path(trace_reduce.find_xplane(d)).read_bytes()
        root = f"{HERE.parent.parent.parent}/".encode()
        mark = (b"<checkout" + b"-" * len(root))[: len(root) - 2] + b">/"
        (out / "scoped.xplane.pb").write_bytes(raw.replace(root, mark))
    (out / "scoped.scopes.json").write_text(json.dumps(scopes, indent=1, sort_keys=True))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    out = Path(ap.parse_args().out)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_trace.py: no TPU; nothing recorded")
    record(out)


if __name__ == "__main__":
    main()
