"""Record the small chip trace that tests/test_trace_reduce.py reads.

    python3 benchmarks/chip/tests/record_trace.py --out <dir>

Runs on a TPU only.  Two CP jobs of two iterations each on a small tensor,
inside the benchmark's own `bench.window` / `bench.job` annotations and
with the program's tracer on, as run.py traces its window.  Writes
`<dir>/small.xplane.pb` and `<dir>/summary.json`: every plane and line with
its event count, and the most frequent event names of each line with one
event's stats, for reading a trace by hand.  The checkout's path, which the
trace's source locations carry, is replaced byte for byte by a placeholder
of the same length, so the file still parses and names no machine.
"""
import argparse
import collections
import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent.parent / "src"))

import jax  # noqa: E402

import tensors  # noqa: E402
import trace_reduce  # noqa: E402

CONFIG = {"shape": [2000, 1500, 3000], "nnz": 50000, "skew": [1.1, 1.1, 1.1], "structure_seed": 0}


def summary(path: str) -> dict:
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names = collections.Counter(ev.name for ev in line.events)
            samples = {}
            for ev in line.events:
                if ev.name in samples or len(samples) >= 40:
                    continue
                samples[ev.name] = {k: (v if isinstance(v, (int, float)) else str(v)[:300])
                                    for k, v in ev.stats}
            lines[line.name] = {"events": sum(names.values()),
                                "top": names.most_common(40), "stats": samples}
        out[plane.name] = lines
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    out = Path(ap.parse_args().out)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py: no TPU; nothing recorded")
    from repro.api import decompose
    from repro.core.coo import SparseTensor
    from repro.kernels.ops import make_planned_cp_als
    from repro.obs import trace as program_trace

    idx, vals, shape = tensors.generate(CONFIG, 1)
    st = SparseTensor(idx, vals, shape)
    ws = make_planned_cp_als(st, 16)
    decompose(st, 16, planned=ws, iters=2, seed=0)  # compile outside the trace
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        program_trace.enable()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for seed in (1, 2):
                with jax.profiler.TraceAnnotation("bench.job"):
                    state = decompose(st, 16, planned=ws, iters=2, seed=seed)
                    jax.block_until_ready(state.factors)
        program_trace.disable()
        jax.profiler.stop_trace()
        raw = Path(trace_reduce.find_xplane(d)).read_bytes()
        root = f"{HERE.parent.parent.parent}/".encode()
        mark = (b"<checkout" + b"-" * len(root))[: len(root) - 2] + b">/"
        (out / "small.xplane.pb").write_bytes(raw.replace(root, mark))
    (out / "summary.json").write_text(json.dumps(summary(str(out / "small.xplane.pb")), indent=1))
    red = trace_reduce.reduce(trace_reduce.load(str(out / "small.xplane.pb")), chips=1)
    print(json.dumps(red.__dict__))


if __name__ == "__main__":
    main()
