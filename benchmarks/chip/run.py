"""Chip benchmark of sparse tensor decomposition: one cell, one run.

    python3 benchmarks/chip/run.py --workload nell2.cp.steady --seed 7 \
        --seconds 30 --trace 0

A cell (`workloads` in BENCHMARK.json) names a configuration
(`configs/<name>.json`: a tensor's shape, nonzeros and skew) and a traffic
mix (`traffic/<name>.json`: format, rank, iterations per job, the
program's workspace builder and its method, `"pallas"` where it names
none; a `"pallas_sharded"` workspace spans the cell's chips).  Everything
else is found by name: the format's compulsory work (`costs/<format>.py`),
its plain reference (`reference/<format>.py`), the cell's limits
(`limits/<workload>.json`) and one reader per metric (`metrics/<metric>.py`).

Set-up generates the tensor from --seed (`tensors.py`), builds the
program's workspace (one plan per mode, layouts to the device) and runs a
two-iteration warm-up job, which compiles every program the window runs.
The window then runs `repro.api.decompose` jobs back to back, closed loop,
each from its own seed, until --seconds have passed; the last job started
runs to its end and counts.  Afterwards a job drawn from the seed is
checked against the float64 reference (`check.py`).  --trace 1 records a
profiler trace of the window and reports the per-layer metrics in place of
the end-to-end ones.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, with --trace 1 breakdown, and last the compared
numbers beside their limits, which also end standard error.  Off a TPU, or
with fewer chips than the cell asks for, the run stops before any work and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
WARMUP_ITERS = 2  # CP's first-iteration variant and its steady one
# Every program JAX fetches from the persistent cache or compiles fires
# COMPILE_EVENT; a fetch also fires CACHE_HIT_EVENT.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_callable(spec: str):
    module, _, attr = spec.partition(":")
    return getattr(importlib.import_module(module), attr)


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def job_seed(seed: int, job: int) -> int:
    """A 31-bit seed for job `job` of a run seeded `seed`."""
    state = np.random.SeedSequence([seed % 2**64, job]).generate_state(1)[0]
    return int(state) & 0x7FFFFFFF


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program cached, source paths made relative to the
    checkout (a Mosaic kernel's body names them, and it is part of the
    key), so a second run, or a checkout at another path, compiles
    nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR.name))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{root}{os.sep}"))


class CompileCounter:
    """Counts programs JAX fetched or compiled (`calls`) and those of them
    the persistent cache served (`hits`): calls - hits were compiled."""

    def __init__(self):
        import jax.monitoring as mon

        self.calls = self.hits = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def snapshot(self) -> tuple[int, int]:
        return self.calls, self.hits

    def _event(self, name, **_):
        if name == CACHE_HIT_EVENT:
            self.hits += 1

    def _duration(self, name, _secs, **_):
        if name == COMPILE_EVENT:
            self.calls += 1


def placement(traffic: dict, chips: int) -> tuple[str, dict]:
    """The program's method (`"pallas"` where the traffic names none) and
    where it runs: the sharded path over the cell's `chips` devices, as the
    workspace builder and every `decompose` call are told."""
    method = traffic.get("method", "pallas")
    return method, ({"devices": chips} if method == "pallas_sharded" else {})


def layouts(ws) -> tuple[list, int, int]:
    """(the layouts' device arrays, the slots the kernels walk, the true
    nonzeros), over every mode of a workspace.  A sharded mode's stack runs
    each of its D shards for the widest shard's NB blocks of blk slots, so
    its slots are D x NB x blk and shard imbalance counts as padding."""
    stacks = getattr(ws, "stacks", None)
    if stacks is not None:
        return ([s.tree() for s in stacks.values()],
                sum(s.nshards * s.nblocks * s.blk for s in stacks.values()),
                sum(sum(s.shard_nnz) for s in stacks.values()))
    plans = [op.plan for op in ws.ops.values()]
    return ([op.layout for op in ws.ops.values()],
            sum(p.nblocks * p.blk for p in plans), sum(p.nnz for p in plans))


def device_record(devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    return {"platform": used[0].platform, "kind": used[0].device_kind, "count": len(used),
            "memory_peak_bytes": int(max(peaks))}


def run(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, data_dir: Path = HERE, log=print) -> dict:
    """Set-up, window and check of one cell; returns the result object."""
    import jax

    from repro.api import decompose
    from repro.core.coo import SparseTensor

    import check
    import tensors
    import trace_reduce

    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    config = load_json(data_dir / "configs" / f"{cell['config']}.json")
    traffic = load_json(data_dir / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(data_dir / "limits" / f"{workload}.json")["limits"]
    fmt, iters = traffic["format"], int(traffic["iters_per_job"])
    if iters < 2:
        raise ValueError("iters_per_job must be >= 2: the check replays all but the last")
    rank = traffic["rank"] if isinstance(traffic["rank"], int) else tuple(traffic["rank"])
    costs = importlib.import_module(f"costs.{fmt}")
    readers = {m["name"]: (load_reader(m["name"]), m["unit"])
               for m in bench["per_layer" if trace else "end_to_end"]}
    devices = jax.devices()
    peaks = load_json(HERE / "peaks.json")
    kind = devices[0].device_kind
    peak = peaks.get(kind)
    if trace and peak is None:
        raise ValueError(f"no peaks for device kind {kind!r} in peaks.json")
    counter = CompileCounter()

    idx, vals, shape = tensors.generate(config, seed)
    st = SparseTensor(idx, vals, shape)
    chips = cell["chips"]
    method, place = placement(traffic, chips)
    job = dict(format=fmt, method=method, iters=iters, tol=traffic["tol"], **place,
               **traffic.get("options", {}))

    t0 = time.perf_counter()
    ws = load_callable(traffic["workspace"])(st, rank, **place)
    arrays, slots, nnz = layouts(ws)
    jax.block_until_ready(arrays)
    plan_build_s = time.perf_counter() - t0
    padded = slots - nnz
    spans = getattr(ws, "nshards", 1)
    if spans != chips:
        raise ValueError(f"{workload}: the workspace spans {spans} device(s); "
                         f"the cell asks for {chips}")

    check.state_arrays(decompose(st, rank, planned=ws, seed=job_seed(seed, 2**32),
                                 **{**job, "iters": WARMUP_ITERS}))

    if trace:
        from repro.obs import trace as program_trace

        trace_dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=options)
        program_trace.enable()

    setup_counts = counter.snapshot()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    results, job_s, iterations = [], [], 0
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        while True:
            s = job_seed(seed, len(results))
            t_job = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.job"):
                state = decompose(st, rank, planned=ws, seed=s, **job)
                jax.block_until_ready([getattr(state, k, None) for k in check.STATE_KEYS])
            results.append((s, state))
            job_s.append(time.perf_counter() - t_job)
            iterations += len(state.fit_history)
            if time.perf_counter() - t_window >= seconds:
                break
    window_s = time.perf_counter() - t_window
    window_counts = tuple(b - a for a, b in zip(setup_counts, counter.snapshot()))

    reduction = None
    if trace:
        program_trace.disable()
        jax.profiler.stop_trace()
        with jax.profiler.TraceAnnotation("bench.reduce"):
            reduction = trace_reduce.reduce(
                trace_reduce.load(trace_reduce.find_xplane(trace_dir.name)), chips=chips)
        trace_dir.cleanup()
    device = device_record(devices, chips)

    # The check: every job's fits; then one job, drawn from the seed, in full.
    attempted = len(results)
    failed = sum(1 for _, done in results
                 if len(done.fit_history) != iters or not np.isfinite(done.fit_history).all())
    pick = int(np.random.default_rng(seed % 2**64).integers(attempted))
    s_pick, picked = results[pick]
    replay = decompose(st, rank, planned=ws, seed=s_pick, **{**job, "iters": iters - 1})
    replay_gap = float(np.max(np.abs(np.asarray(replay.fit_history, np.float64)
                                     - np.asarray(picked.fit_history[: iters - 1], np.float64))))
    before, after = check.state_arrays(replay), check.state_arrays(picked)
    reported_fit = float(picked.fit_history[-1])
    del ws, results, replay, picked, state
    gc.collect()
    gaps = check.program_gaps(fmt, idx, vals, before, after, reported_fit)
    gaps["replay_gap"] = replay_gap
    ok, checks = check.judge(gaps, {"replay_gap": 0.0, **limits})
    log(f"[bench] {workload} seed {seed}: {iterations} iterations in {attempted} jobs, "
        f"{window_s:.3f} s window; setup {setup_s:.3f} s (plan build {plan_build_s:.3f} s, "
        f"{setup_counts[0]} programs, {setup_counts[0] - setup_counts[1]} compiled, the rest "
        f"from the cache); in the window {window_counts[0]} programs; job {pick} checked; other gaps "
        + ", ".join(f"{k} {v:.3e}" for k, v in gaps.items() if k not in checks))
    log("[bench] job seconds " + " ".join(f"{t:.4f}" for t in job_s))

    work = costs.kernel_work(shape, st.nnz, rank)
    bound_s = None
    if peak is not None:
        per_mode = [(w["bytes"] / peak["hbm_bytes_per_s"], w["flops"] / peak["flops_per_s"])
                    for w in work]
        # The whole tensor's work against the peaks of all the cell's chips.
        bound_s = sum(max(b, f) for b, f in per_mode) / chips
        log(f"[bench] compulsory kernel work per iteration "
            f"{sum(w['bytes'] for w in work):,} B and {sum(w['flops'] for w in work):,} op: "
            f"{'bytes bind' if all(b >= f for b, f in per_mode) else 'operations bind'} "
            f"the roofline at {bound_s * 1e6:.3f} us on {chips} chip(s)")
    ctx = types.SimpleNamespace(
        window_s=window_s, iterations=iterations, setup_s=setup_s,
        hbm_peak_bytes=device["memory_peak_bytes"], plan_build_s=plan_build_s,
        slots=slots, padded_slots=padded, trace=reduction, bound_s=bound_s,
    )
    metrics = {}
    for name, (read, unit) in readers.items():
        value = read(ctx)
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": float(value), "unit": unit}

    result = {
        "correct": bool(ok and failed == 0),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
        "compiles": {"setup": setup_counts[0] - setup_counts[1], "setup_cached": setup_counts[1],
                     "window": window_counts[0]},
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.device_ops,
                               "idle_gaps": reduction.idle_gaps}
        log(f"[bench] trace, per chip: window {reduction.window_s:.6f} s, busy "
            f"{reduction.busy_s:.6f} s, kernel {reduction.kernel_s:.6f} s in "
            f"{reduction.kernel_events} events, collectives {reduction.collective_s:.6f} s")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == a.workload), None)
    if cell is None:
        print(f"run.py: no workload {a.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: {a.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s); nothing run", file=sys.stderr)
        return 3
    enable_compile_cache(ROOT)
    result = run(bench, a.workload, a.seed, a.seconds, bool(a.trace), t_start=T_START,
                 log=lambda msg: print(msg, file=sys.stderr, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']:.6e} <= {c['limit']:.6e} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
