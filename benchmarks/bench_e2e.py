"""End-to-end fast-path benchmark: layout-generation cost (Tensor Remapper),
steady-state ALS iteration wall-clock, and the mttkrp_auto plan-cache — the
three quantities the paper (and GenTen / the authors' GPU follow-on) treat as
first-class measurements.  Writes the persistent trajectory file
`BENCH_kernel.json` at the repo root (schema: repro/bench.py) so every future
PR has a perf baseline to move.

Sections
  plan_build_*   `plan_blocks` (vectorized scatter build) vs
                 `plan_blocks_reference` (the per-group Python loop it
                 replaced), at two DMA block sizes.  blk=32 is the
                 many-small-groups regime where the interpreter loop dominates
                 (medium: ~200k groups); blk=256 also pays the padded-layout
                 materialization floor (99% padding on medium), which bounds
                 the achievable full-call speedup by memory bandwidth.
  als_iter_*     one full jitted ALS iteration (every mode's MTTKRP -> gram ->
                 solve -> normalize + on-device fit) for the planned Pallas
                 path (interpret mode on CPU) and the pure-JAX approaches.
  plan_cache     mttkrp_auto(method='pallas') keyed plan cache: first vs
                 cached call, hit/miss counters (mttkrp kind).
  tucker_*       the second workload on the same substrate: PlannedTucker
                 plan-build time, one jitted HOOI iteration (every mode's
                 TTMc -> Gram eigh -> factor update + core/fit), and the
                 tucker_auto side of the kind-keyed plan cache.
  tt_*           the third workload: PlannedTT plan-build time, one jitted
                 TT-ALS sweep (every mode's TT-core kernel -> kron(P,Q)
                 normal solve -> core update + fit), and the tt_auto side
                 of the kind-keyed plan cache.
  guard_overhead the resilience guards (repro.resilience) on the drive
                 loop: per-iteration wall-clock with guards off vs
                 GuardConfig(check_factors_every=1) — the fit-based
                 divergence tracker rides the existing host sync for free,
                 so the delta is one stacked isfinite reduction + sync per
                 iteration.  Acceptance: < 5% on als_iter_pallas.
  sharded_*      the distributed planned path (repro.dist.planned) on a
                 forced multi-device CPU host platform: workspace build
                 (per-mode partitions + shard-local layouts), one jitted
                 shard_map ALS sweep, and the partition balance.  Runs in a
                 subprocess because XLA_FLAGS=--xla_force_host_platform_
                 device_count must be set before jax initializes.
  pms_accuracy_* predicted-vs-achieved PMS accounting (repro.obs.calibrate):
                 each format's exact per-plan roofline prediction
                 (`pms_estimates` summed over modes) joined against the
                 measured steady-state sweep, reported as predicted_s /
                 measured_s / achieved_pct per (format, preset).  On CPU
                 interpret-mode Pallas achieved_pct is far below 100 (the
                 model describes TPU hardware); its trajectory across PRs is
                 the regression signal.  The medium preset pins a
                 big-input-tile config (PMS_MEDIUM_CFG) — the default
                 256-cube tiles put ~470k grid steps per sweep through the
                 interpreter, which is hours, while 4096-row input tiles
                 collapse that to a few thousand blocks.
  pms_calibration  default-spec vs measured-spec accounting (repro.tune):
                 a TPUSpec is fitted to this machine (microbenchmarks +
                 block-sweep least squares) and one measured CP sweep is
                 joined against the roofline prediction under both specs —
                 the measured spec's achieved_pct must land strictly closer
                 to 100% (docs/autotune.md).

  PYTHONPATH=src python benchmarks/bench_e2e.py [--fast] [--out PATH]

Non-clobber contract: the committed BENCH_kernel.json at the repo root is
the *full-run* baseline trajectory.  `--fast` (the CI smoke subset) and
`benchmarks/run.py --quick` must never overwrite it — `main` refuses the
baseline path in fast mode (see `_resolve_out`), instead of relying on the
caller picking a scratch path by convention.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench import result_record, write_report
from repro.core.coo import frostt_like, random_factors
from repro.core.cp_als import _sweep_streams
from repro.core.memctrl import CacheEngineConfig, MemoryControllerConfig
from repro.core.remap import plan_blocks, plan_blocks_reference
from repro.kernels import ops
from repro.platform import enable_compile_cache

ROOT = Path(__file__).resolve().parent.parent
BASELINE_PATH = ROOT / "BENCH_kernel.json"

# The medium-preset calibration config: interpret-mode wall clock tracks the
# grid-step count, and medium at the default 256-cube tiles is ~470k steps
# per sweep (hours on the CPU interpreter).  4096-row input tiles keep the
# same stream and collapse the block count to a few thousand.
PMS_MEDIUM_CFG = MemoryControllerConfig(
    cache=CacheEngineConfig(tile_i=256, tile_j=4096, tile_k=4096)
)


def _resolve_out(out: str | None, fast: bool) -> Path:
    """Enforce the non-clobber contract: fast/scratch runs may write anywhere
    EXCEPT the committed full-run baseline at the repo root."""
    path = Path(out) if out else BASELINE_PATH
    if fast and path.resolve() == BASELINE_PATH.resolve():
        raise SystemExit(
            f"refusing to overwrite the committed full-run baseline "
            f"{BASELINE_PATH} with a --fast subset: pass --out <scratch path> "
            f"(benchmarks/run.py --quick uses a tempdir), or run without "
            f"--fast to regenerate the baseline"
        )
    return path

# blk=256 is the kernel default; blk=32 is the layout-generation stress regime
# (groups on the scaled presets hold only a few non-zeros each, so the padded
# output stays small and the per-group loop is the whole cost).
PLAN_CONFIGS = (("blk256", 256), ("blk32", 32))


def _norm_x_sq(st) -> jax.Array:
    return jnp.asarray(float(np.sum(st.values.astype(np.float64) ** 2)), jnp.float32)


def bench_plan_build(presets, results, reps: int):
    print("== plan build: vectorized plan_blocks vs reference loop")
    for preset in presets:
        st = frostt_like(preset)
        for cname, blk in PLAN_CONFIGS:
            t_vec = min(
                _timed(lambda: plan_blocks(st, 0, blk=blk)) for _ in range(reps)
            )
            ref_reps = min(2, reps) if preset in ("medium", "large") else reps
            t_ref = min(
                _timed(lambda: plan_blocks_reference(st, 0, blk=blk))
                for _ in range(ref_reps)
            )
            speedup = t_ref / t_vec
            name = f"plan_build_{cname}"
            results += [
                result_record(name, preset, "reference_s", t_ref, "s"),
                result_record(name, preset, "vectorized_s", t_vec, "s"),
                result_record(name, preset, "speedup_x", speedup, "x"),
            ]
            print(f"  {preset:10s} {cname:7s} reference={t_ref:8.3f}s "
                  f"vectorized={t_vec:8.3f}s  speedup={speedup:6.1f}x")


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_als_iter(presets, results, rank: int, reps: int):
    print("== steady-state ALS iteration (one jitted sweep, all modes + fit)")
    key = jax.random.PRNGKey(0)
    for preset in presets:
        st = frostt_like(preset)
        nxs = _norm_x_sq(st)

        # Planned Pallas path (interpret mode on CPU — the BlockSpec DMA
        # schedule is the TPU performance model; wall-clock here tracks the
        # grid-step count, not MXU throughput).
        ws = ops.make_planned_cp_als(st, rank)
        facs = ws.pad_factors(random_factors(key, st.shape, rank))
        idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
        facs, lam, fit = ws.sweep(facs, idx, val, nxs, first=True)
        facs, lam, fit = ws.sweep(facs, idx, val, nxs, first=False)  # compile steady state
        jax.block_until_ready(fit)
        t0 = time.perf_counter()
        for _ in range(reps):
            facs, lam, fit = ws.sweep(facs, idx, val, nxs, first=False)
        jax.block_until_ready(fit)
        t_pallas = (time.perf_counter() - t0) / reps
        results.append(result_record("als_iter_pallas", preset, "iter_s", t_pallas, "s"))
        print(f"  {preset:10s} pallas iter={t_pallas:8.3f}s "
              f"(plans: {ws.plan_bytes()/2**20:.1f} MiB)")

        streams = [st.sorted_by(m) for m in range(st.nmodes)]
        sidx = tuple(jnp.asarray(s.indices) for s in streams)
        sval = tuple(jnp.asarray(s.values) for s in streams)
        for method in ("approach1", "approach2"):
            ft = tuple(random_factors(key, st.shape, rank))
            ft, lam, fit = _sweep_streams(
                ft, sidx, sval, nxs, shape=st.shape, method=method, first=True)
            ft, lam, fit = _sweep_streams(
                ft, sidx, sval, nxs, shape=st.shape, method=method, first=False)
            jax.block_until_ready(fit)
            t0 = time.perf_counter()
            for _ in range(reps):
                ft, lam, fit = _sweep_streams(
                    ft, sidx, sval, nxs, shape=st.shape, method=method, first=False)
            jax.block_until_ready(fit)
            t = (time.perf_counter() - t0) / reps
            results.append(result_record(f"als_iter_{method}", preset, "iter_s", t, "s"))
            print(f"  {preset:10s} {method:17s} iter={t:8.3f}s")


def bench_guard_overhead(results, preset: str, rank: int, iters: int):
    """Numerical guards on the steady-state drive loop (same sweep the
    als_iter_pallas section times, driven through `PlannedWorkspace.drive`):
    guards off vs the heaviest cadence (check_factors_every=1)."""
    print("== guard overhead (drive loop, guards off vs check_factors_every=1)")
    from repro.core.loop import GuardConfig

    st = frostt_like(preset)
    f0 = random_factors(jax.random.PRNGKey(0), st.shape, rank)
    idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
    nxs = _norm_x_sq(st)
    ws = ops.make_planned_cp_als(st, rank)
    gc = GuardConfig(policy="raise", check_factors_every=1)
    ws.drive(f0, (idx, val, nxs), iters=2)  # compile first + steady sweeps
    ws.drive(f0, (idx, val, nxs), iters=2, guards=gc)  # + the finite check
    t_off = min(
        _timed(lambda: ws.drive(f0, (idx, val, nxs), iters=iters))
        for _ in range(2)
    ) / iters
    t_on = min(
        _timed(lambda: ws.drive(f0, (idx, val, nxs), iters=iters, guards=gc))
        for _ in range(2)
    ) / iters
    frac = (t_on - t_off) / t_off
    results += [
        result_record("guard_overhead", preset, "iter_off_s", t_off, "s"),
        result_record("guard_overhead", preset, "iter_on_s", t_on, "s"),
        result_record("guard_overhead", preset, "overhead_frac", frac, "ratio"),
    ]
    print(f"  {preset:10s} off={t_off:.3f}s on={t_on:.3f}s "
          f"overhead={frac:+.1%}")


def bench_plan_cache(results, preset: str, rank: int):
    print("== mttkrp_auto plan cache (keyed on tensor fingerprint)")
    st = frostt_like(preset)
    facs = random_factors(jax.random.PRNGKey(0), st.shape, rank)
    ops.plan_cache_clear()
    t_first = _timed(lambda: jax.block_until_ready(ops.mttkrp_auto(st, facs, 0)))
    t_cached = min(
        _timed(lambda: jax.block_until_ready(ops.mttkrp_auto(st, facs, 0)))
        for _ in range(2)
    )
    stats = ops.plan_cache_stats()
    results += [
        result_record("plan_cache", preset, "first_call_s", t_first, "s"),
        result_record("plan_cache", preset, "cached_call_s", t_cached, "s"),
        result_record("plan_cache", preset, "hits", stats["hits"], "count"),
        result_record("plan_cache", preset, "misses", stats["misses"], "count"),
    ]
    print(f"  {preset:10s} first={t_first:.3f}s cached={t_cached:.3f}s "
          f"hits={stats['hits']} misses={stats['misses']}")


def bench_tucker(results, presets, core_rank: int, reps: int):
    """Sparse Tucker HOOI on the planned TTM-chain kernel: layout-build cost,
    steady-state jitted iteration, and the ttmc side of the plan cache."""
    print("== tucker: plan build / jitted HOOI iteration / tucker_auto cache")
    from repro.tucker import init_tucker_factors, make_planned_tucker

    key = jax.random.PRNGKey(0)
    for preset in presets:
        st = frostt_like(preset)
        ranks = (core_rank,) * st.nmodes
        nxs = _norm_x_sq(st)

        built = []
        t_plan = _timed(lambda: built.append(make_planned_tucker(st, ranks)))
        ws = built[0]
        facs = ws.pad_factors(init_tucker_factors(key, st.shape, ranks))
        facs, core, fit = ws.sweep(facs, nxs)
        facs, core, fit = ws.sweep(facs, nxs)  # compile + steady state
        jax.block_until_ready(fit)
        t0 = time.perf_counter()
        for _ in range(reps):
            facs, core, fit = ws.sweep(facs, nxs)
        jax.block_until_ready(fit)
        t_iter = (time.perf_counter() - t0) / reps
        results += [
            result_record("tucker_plan_build", preset, "plan_s", t_plan, "s"),
            result_record("tucker_hooi_iter", preset, "iter_s", t_iter, "s"),
        ]
        print(f"  {preset:10s} plan={t_plan:8.3f}s hooi iter={t_iter:8.3f}s "
              f"(plans: {ws.plan_bytes()/2**20:.1f} MiB, core ranks {ranks})")

    # kind-keyed plan cache, ttmc side (mirrors bench_plan_cache)
    st = frostt_like("tiny")
    facs = random_factors(jax.random.PRNGKey(0), st.shape, core_rank)
    ops.plan_cache_clear()
    t_first = _timed(lambda: jax.block_until_ready(ops.tucker_auto(st, facs, 0)))
    t_cached = min(
        _timed(lambda: jax.block_until_ready(ops.tucker_auto(st, facs, 0)))
        for _ in range(2)
    )
    stats = ops.plan_cache_stats()["by_kind"]["ttmc"]
    results += [
        result_record("tucker_plan_cache", "tiny", "first_call_s", t_first, "s"),
        result_record("tucker_plan_cache", "tiny", "cached_call_s", t_cached, "s"),
        result_record("tucker_plan_cache", "tiny", "hits", stats["hits"], "count"),
        result_record("tucker_plan_cache", "tiny", "misses", stats["misses"], "count"),
    ]
    print(f"  tiny       first={t_first:.3f}s cached={t_cached:.3f}s "
          f"hits={stats['hits']} misses={stats['misses']} (ttmc kind)")


def bench_tt(results, presets, bond_rank: int, reps: int):
    """Tensor-train ALS on the planned TT-core kernel: layout-build cost,
    steady-state jitted sweep, and the tt side of the plan cache."""
    print("== tt: plan build / jitted TT-ALS sweep / tt_auto cache")
    from repro.tt import core_to_matrix, init_tt_cores, make_planned_tt

    key = jax.random.PRNGKey(0)
    for preset in presets:
        st = frostt_like(preset)
        tt_ranks = (bond_rank,) * (st.nmodes - 1)
        nxs = _norm_x_sq(st)

        built = []
        t_plan = _timed(lambda: built.append(make_planned_tt(st, tt_ranks)))
        ws = built[0]
        cores = init_tt_cores(key, st.shape, tt_ranks)
        facs = ws.pad_factors([core_to_matrix(c) for c in cores])
        idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
        facs, _, fit = ws.sweep(facs, idx, val, nxs)
        facs, _, fit = ws.sweep(facs, idx, val, nxs)  # compile + steady state
        jax.block_until_ready(fit)
        t0 = time.perf_counter()
        for _ in range(reps):
            facs, _, fit = ws.sweep(facs, idx, val, nxs)
        jax.block_until_ready(fit)
        t_iter = (time.perf_counter() - t0) / reps
        results += [
            result_record("tt_plan_build", preset, "plan_s", t_plan, "s"),
            result_record("tt_als_iter", preset, "iter_s", t_iter, "s"),
        ]
        print(f"  {preset:10s} plan={t_plan:8.3f}s tt-als iter={t_iter:8.3f}s "
              f"(plans: {ws.plan_bytes()/2**20:.1f} MiB, bond ranks {tt_ranks})")

    # kind-keyed plan cache, tt side (mirrors bench_plan_cache)
    st = frostt_like("tiny")
    cores = init_tt_cores(jax.random.PRNGKey(0), st.shape, (bond_rank,) * (st.nmodes - 1))
    ops.plan_cache_clear()
    t_first = _timed(lambda: jax.block_until_ready(ops.tt_auto(st, cores, 0)))
    t_cached = min(
        _timed(lambda: jax.block_until_ready(ops.tt_auto(st, cores, 0)))
        for _ in range(2)
    )
    stats = ops.plan_cache_stats()["by_kind"]["tt"]
    results += [
        result_record("tt_plan_cache", "tiny", "first_call_s", t_first, "s"),
        result_record("tt_plan_cache", "tiny", "cached_call_s", t_cached, "s"),
        result_record("tt_plan_cache", "tiny", "hits", stats["hits"], "count"),
        result_record("tt_plan_cache", "tiny", "misses", stats["misses"], "count"),
    ]
    print(f"  tiny       first={t_first:.3f}s cached={t_cached:.3f}s "
          f"hits={stats['hits']} misses={stats['misses']} (tt kind)")


def _sharded_sweep_record(preset: str, rank: int, devices: int, reps: int) -> dict:
    """Build the sharded planned CP-ALS workspace over `devices` devices and
    time its steady-state sweep."""
    from repro.dist.sharding import stream_imbalance

    st = frostt_like(preset)
    t0 = time.perf_counter()
    ws = ops.make_sharded_planned_cp_als(st, rank, devices=devices)
    t_build = time.perf_counter() - t0
    facs = ws.pad_factors(random_factors(jax.random.PRNGKey(0), st.shape, rank))
    nxs = jnp.asarray(float(np.sum(st.values.astype(np.float64) ** 2)), jnp.float32)
    facs, lam, fit = ws.sweep(facs, nxs, first=True)
    facs, lam, fit = ws.sweep(facs, nxs, first=False)  # compile steady state
    jax.block_until_ready(fit)
    t0 = time.perf_counter()
    for _ in range(reps):
        facs, lam, fit = ws.sweep(facs, nxs, first=False)
    jax.block_until_ready(fit)
    return {
        "build_s": t_build,
        "iter_s": (time.perf_counter() - t0) / reps,
        "imbalance_x": stream_imbalance(ws.stacks[0].shard_nnz),
        "plan_mib": ws.plan_bytes() / 2**20,
    }


_SHARDED_CHILD = (
    "import json; from benchmarks.bench_e2e import _sharded_sweep_record; "
    "print('RESULT ' + json.dumps(_sharded_sweep_record({preset!r}, {rank}, "
    "{devices}, {reps})))"
)


def _steady_sweep_s(step, reps: int) -> float:
    """Steady-state seconds per sweep: two throwaway calls (compile + warm),
    then the mean of `reps` timed calls."""
    jax.block_until_ready(step())
    jax.block_until_ready(step())
    t0 = time.perf_counter()
    for _ in range(reps):
        fit = step()
    jax.block_until_ready(fit)
    return (time.perf_counter() - t0) / reps


def bench_pms_accuracy(results, presets, rank: int, core_rank: int,
                       bond_rank: int, reps: int):
    """Predicted-vs-achieved PMS accounting (repro.obs.calibrate): every
    format's exact per-plan prediction joined against its measured
    steady-state sweep on the same built workspace."""
    print("== pms accuracy: exact roofline prediction vs measured sweep")
    from repro.obs.calibrate import accuracy_records, calibration_row
    from repro.tt import core_to_matrix, init_tt_cores, make_planned_tt
    from repro.tucker import init_tucker_factors, make_planned_tucker

    key = jax.random.PRNGKey(0)
    rows = []
    for preset in presets:
        st = frostt_like(preset)
        nxs = _norm_x_sq(st)
        idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
        cfg = PMS_MEDIUM_CFG if preset == "medium" else None
        local_reps = 1 if preset == "medium" else reps

        ws = ops.make_planned_cp_als(st, rank, cfg=cfg)
        state = {"f": ws.pad_factors(random_factors(key, st.shape, rank))}

        def step_cp():
            state["f"], _, fit = ws.sweep(state["f"], idx, val, nxs, first=False)
            return fit

        rows.append(calibration_row(
            ws, _steady_sweep_s(step_cp, local_reps),
            format="cp", preset=preset,
        ))

        ranks = (core_rank,) * st.nmodes
        ws = make_planned_tucker(st, ranks, cfg=cfg)
        state = {"f": ws.pad_factors(init_tucker_factors(key, st.shape, ranks))}

        def step_tk():
            state["f"], _, fit = ws.sweep(state["f"], nxs)
            return fit

        rows.append(calibration_row(
            ws, _steady_sweep_s(step_tk, local_reps),
            format="tucker", preset=preset,
        ))

        tt_ranks = (bond_rank,) * (st.nmodes - 1)
        ws = make_planned_tt(st, tt_ranks, cfg=cfg)
        cores = init_tt_cores(key, st.shape, tt_ranks)
        state = {"f": ws.pad_factors([core_to_matrix(c) for c in cores])}

        def step_tt():
            state["f"], _, fit = ws.sweep(state["f"], idx, val, nxs)
            return fit

        rows.append(calibration_row(
            ws, _steady_sweep_s(step_tt, local_reps),
            format="tt", preset=preset,
        ))

    results += accuracy_records(rows)
    for r in rows:
        print(f"  {r.preset:10s} {r.format:7s} predicted={r.predicted_s:.3e}s "
              f"measured={r.measured_s:8.3f}s achieved={r.achieved_pct:.4f}%")


def bench_pms_calibration(results, preset: str, rank: int, reps: int):
    """Default-spec vs measured-spec PMS accounting (repro.tune): fit a
    TPUSpec to this machine (microbenchmarks + block-sweep least squares),
    then join ONE measured CP sweep on `preset` against the roofline
    prediction under both specs.  Acceptance (ISSUE 10): the measured spec's
    achieved_pct is strictly closer to 100% than the default's — the
    datasheet constants describe TPU silicon, not the backend that actually
    ran."""
    print("== pms calibration: default vs measured TPUSpec achieved_pct")
    from repro.obs.calibrate import calibration_row
    from repro.tune import calibrate

    cal = calibrate(preset="tiny", reps=reps)
    st = frostt_like(preset)
    nxs = _norm_x_sq(st)
    idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
    ws = ops.make_planned_cp_als(st, rank)
    state = {"f": ws.pad_factors(random_factors(jax.random.PRNGKey(0), st.shape, rank))}

    def step():
        state["f"], _, fit = ws.sweep(state["f"], idx, val, nxs, first=False)
        return fit

    measured_s = _steady_sweep_s(step, reps)
    default = calibration_row(ws, measured_s, format="cp", preset=preset)
    measured = calibration_row(
        ws, measured_s, format="cp", preset=preset, spec=cal.spec
    )
    results += [
        result_record("pms_calibration", preset, "measured_sweep_s", measured_s, "s"),
        result_record("pms_calibration", preset, "achieved_pct_default",
                      default.achieved_pct, "%"),
        result_record("pms_calibration", preset, "achieved_pct_measured",
                      measured.achieved_pct, "%"),
        result_record("pms_calibration", preset, "hbm_bw_fitted",
                      cal.spec.hbm_bw, "B/s"),
        result_record("pms_calibration", preset, "peak_flops_f32_fitted",
                      cal.spec.peak_flops_f32, "flop/s"),
    ]
    closer = abs(measured.achieved_pct - 100) < abs(default.achieved_pct - 100)
    print(f"  {preset:10s} sweep={measured_s:8.3f}s "
          f"achieved: default={default.achieved_pct:.4f}% "
          f"measured={measured.achieved_pct:.1f}% "
          f"({'measured closer to 100%' if closer else 'NOT closer — check fit'})")


def bench_sharded(results, presets, rank: int, devices: int, reps: int):
    """Distributed planned CP-ALS: workspace build, steady-state shard_map
    sweep, and partition balance.  Runs in this process when it already
    sees `devices` devices.  Otherwise, on the CPU only, a child process
    forces that many host devices (the count locks when JAX starts); a
    child could not reach an accelerator this process holds."""
    in_process = jax.device_count() >= devices
    if not in_process and jax.default_backend() != "cpu":
        print(f"== sharded planned path skipped: {jax.device_count()} "
              f"{jax.default_backend()} device(s), {devices} needed")
        return
    print(f"== sharded planned path ({devices} devices, "
          f"{'in process' if in_process else 'forced host devices, subprocess'})")
    for preset in presets:
        if in_process:
            r = _sharded_sweep_record(preset, rank, devices, reps)
        else:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
            env["PYTHONPATH"] = os.pathsep.join((str(ROOT / "src"), str(ROOT)))
            code = _SHARDED_CHILD.format(
                preset=preset, rank=rank, devices=devices, reps=reps
            )
            out = subprocess.run(
                [sys.executable, "-c", code], env=env, capture_output=True,
                text=True, timeout=900, cwd=ROOT,
            )
            if out.returncode != 0:
                raise RuntimeError(
                    f"sharded bench subprocess failed:\n{out.stdout}\n{out.stderr[-3000:]}"
                )
            line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
            r = json.loads(line[len("RESULT "):])
        results += [
            result_record("sharded_plan_build", preset, "build_s", r["build_s"], "s"),
            result_record("sharded_als_iter", preset, "iter_s", r["iter_s"], "s"),
            result_record("sharded_als_iter", preset, "devices", devices, "count"),
            result_record("sharded_partition", preset, "imbalance_x", r["imbalance_x"], "x"),
        ]
        print(f"  {preset:10s} build={r['build_s']:7.3f}s sweep={r['iter_s']:7.3f}s "
              f"imbalance={r['imbalance_x']:.2f}x plans={r['plan_mib']:.1f} MiB "
              f"({devices} devices)")


def main(fast: bool = False, out: str | None = None) -> dict:
    path = _resolve_out(out, fast)
    enable_compile_cache(ROOT)
    plan_presets = ("small", "4d_small", "5d_small") if fast else (
        "small", "medium", "4d_small", "5d_small")
    als_presets = ("small", "4d_small", "5d_small")
    tucker_presets = ("tiny",) if fast else ("small", "4d_small")
    sharded_presets = ("tiny",) if fast else ("tiny", "small")
    reps = 1 if fast else 3
    rank = 16

    results: list[dict] = []
    t0 = time.time()
    bench_plan_build(plan_presets, results, reps=max(2, reps))
    bench_als_iter(als_presets, results, rank=rank, reps=reps)
    bench_plan_cache(results, preset="tiny", rank=rank)
    bench_guard_overhead(results, preset="small", rank=rank,
                         iters=3 if fast else 6)
    bench_tucker(results, tucker_presets, core_rank=4, reps=reps)
    bench_tt(results, tucker_presets, bond_rank=4, reps=reps)
    pms_presets = ("tiny",) if fast else ("small", "medium")
    bench_pms_accuracy(results, pms_presets, rank=rank, core_rank=4,
                       bond_rank=4, reps=reps)
    bench_pms_calibration(results, preset="tiny" if fast else "small",
                          rank=rank, reps=reps)
    bench_sharded(results, sharded_presets, rank=rank, devices=2, reps=reps)

    report = write_report(path, results)
    print(f"[bench_e2e] {len(results)} results -> {path} "
          f"(commit {report['commit'][:12]}, {time.time()-t0:.1f}s total)")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="CI smoke subset")
    ap.add_argument("--out", default=None, help="output path (default: repo-root BENCH_kernel.json)")
    a = ap.parse_args()
    main(fast=a.fast, out=a.out)
