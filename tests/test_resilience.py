"""Resilience layer (repro.resilience + repro.testing.faults): numerical
guards in the planned drive loop, plan integrity validation, HBM admission
control with the graceful-degradation ladder, checkpoint/resume of a killed
sweep, and the bounded plan cache.

Every injected fault from the harness must be DETECTED by the guard built
for it, and every recovery policy must land within tolerance of the clean
run — that pairing is the contract this file asserts."""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.api import decompose
from repro.core.loop import GuardConfig, GuardState, finish_iter
from repro.core.remap import plan_blocks
from repro.kernels import ops
from repro.kernels.ops import make_planned_cp_als
from repro.resilience import (
    AdmissionError,
    DecompositionDiverged,
    PlanValidationError,
    admission_bytes,
    admit,
    plan_with_budget,
    plans_validated,
    reference_footprint_bytes,
    validate_plan,
)
from repro.testing import faults

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITERS = 5


def _clean(st, rank=8, **kw):
    return decompose(st, rank, iters=ITERS, seed=0, **kw)


# ---------------------------------------------------------------------------
# finish_iter NaN semantics (guards off)
# ---------------------------------------------------------------------------


def test_finish_iter_nonfinite_stops_and_warns():
    fits: list = []
    with pytest.warns(RuntimeWarning, match="non-finite fit"):
        stop = finish_iter(fits, float("nan"), 0, None, False, "unit")
    assert stop is True
    assert len(fits) == 1 and not np.isfinite(fits[0])


def test_guards_off_nan_terminates_loop(tiny_tensor):
    """A NaN fit must stop the loop and surface even without guards — the
    pre-fix behavior silently looped to `iters` on NaN."""
    ws = make_planned_cp_als(tiny_tensor, 8)
    faults.inject_nan_factor(ws, at_iter=1)
    with pytest.warns(RuntimeWarning, match="non-finite fit"):
        out = decompose(tiny_tensor, 8, iters=ITERS, seed=0, planned=ws)
    assert len(out.fit_history) < ITERS
    assert not np.isfinite(out.fit_history[-1])


# ---------------------------------------------------------------------------
# GuardConfig / drive-extras contracts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "bad",
    [
        dict(policy="retry"),
        dict(divergence_patience=0),
        dict(max_restarts=-1),
        dict(check_factors_every=-1),
    ],
)
def test_guard_config_validation(bad):
    with pytest.raises(ValueError):
        GuardConfig(**bad)


def test_guard_state_regression_patience():
    gs = GuardState(GuardConfig(divergence_patience=2))
    assert gs.observe_fit(0.5) is None
    assert gs.observe_fit(0.3) is None          # streak 1
    reason = gs.observe_fit(0.2)                # streak 2 -> fires
    assert reason is not None and "regressed" in reason
    gs.reset()
    assert gs.observe_fit(0.1) is None


def test_guards_rejected_on_reference_methods(tiny_tensor):
    with pytest.raises(ValueError, match="guards"):
        decompose(tiny_tensor, 8, iters=2, method="approach1",
                  guards=GuardConfig())


def test_checkpoint_every_requires_path(tiny_tensor):
    with pytest.raises(ValueError, match="checkpoint"):
        decompose(tiny_tensor, 8, iters=2, checkpoint_every=2)


# ---------------------------------------------------------------------------
# Guard policies: detect and recover
# ---------------------------------------------------------------------------


def test_raise_policy_detects_nan(tiny_tensor):
    ws = make_planned_cp_als(tiny_tensor, 8)
    faults.inject_nan_factor(ws, at_iter=1)
    with pytest.raises(DecompositionDiverged) as ei:
        decompose(tiny_tensor, 8, iters=ITERS, seed=0, planned=ws,
                  guards=GuardConfig(policy="raise"))
    assert "non-finite fit" in str(ei.value)
    assert ei.value.fit_history  # diagnostic payload present


def test_factor_cadence_check_fires_at_injection_iter(tiny_tensor):
    """check_factors_every=1 catches the poison in the iteration it lands,
    one iteration earlier than the free fit guard."""
    ws = make_planned_cp_als(tiny_tensor, 8)
    faults.inject_nan_factor(ws, at_iter=1)
    with pytest.raises(DecompositionDiverged) as ei:
        decompose(tiny_tensor, 8, iters=ITERS, seed=0, planned=ws,
                  guards=GuardConfig(policy="raise", check_factors_every=1))
    assert ei.value.iteration == 1
    assert "factor" in ei.value.reason


@pytest.mark.parametrize("policy", ["restart", "fallback"])
@pytest.mark.parametrize("fixture", ["tiny_tensor", "tensor4d", "tensor5d"])
def test_recovery_matches_clean_run(request, fixture, policy):
    """Acceptance: restart and fallback recover to a final fit within 1e-5
    of the uninjected run on the 3/4/5-mode presets."""
    st = request.getfixturevalue(fixture)
    clean = _clean(st)
    ws = make_planned_cp_als(st, 8)
    faults.inject_nan_factor(ws, at_iter=1)
    out = decompose(st, 8, iters=ITERS, seed=0, planned=ws,
                    guards=GuardConfig(policy=policy))
    assert abs(out.fit_history[-1] - clean.fit_history[-1]) < 1e-5


@pytest.mark.parametrize("policy", ["restart", "fallback"])
@pytest.mark.parametrize("format,rank", [("tucker", (4, 4, 4)), ("tt", (4, 3))])
def test_recovery_other_formats(tiny_tensor, format, rank, policy):
    clean = decompose(tiny_tensor, rank, format=format, iters=ITERS, seed=0)
    if format == "tucker":
        from repro.tucker.hooi import make_planned_tucker as make
    else:
        from repro.tt.als import make_planned_tt as make
    ws = make(tiny_tensor, rank)
    faults.inject_nan_factor(ws, at_iter=1)
    out = decompose(tiny_tensor, rank, format=format, iters=ITERS, seed=0,
                    planned=ws, guards=GuardConfig(policy=policy))
    assert abs(out.fit_history[-1] - clean.fit_history[-1]) < 1e-5


def test_restart_budget_exhausted(tiny_tensor):
    """A fault that re-fires on every attempt must exhaust max_restarts and
    escalate instead of looping forever."""
    ws = make_planned_cp_als(tiny_tensor, 8)
    inner = ws._sweep_call

    def always_poisoned(facs, *args, it):
        import jax.numpy as jnp

        facs, aux, fit = inner(facs, *args, it=it)
        return facs, aux, fit * jnp.nan

    ws._sweep_call = always_poisoned
    with pytest.raises(DecompositionDiverged, match="restart budget"):
        decompose(tiny_tensor, 8, iters=ITERS, seed=0, planned=ws,
                  guards=GuardConfig(policy="restart", max_restarts=1))


def test_dead_shard_detected_by_regression_guard(tiny_tensor):
    """A silently dead shard loses its contribution to every psum'd update;
    the fit collapses and the regression guard fires."""
    from repro.dist.planned import make_sharded_planned_cp_als, shard_plan

    ws = make_sharded_planned_cp_als(tiny_tensor, 8, dist=shard_plan(1))
    faults.deaden_shard(ws, shard=0, at_iter=1)
    with pytest.raises(DecompositionDiverged, match="regressed"):
        decompose(tiny_tensor, 8, iters=10, seed=0, method="pallas_sharded",
                  planned=ws,
                  guards=GuardConfig(policy="raise", divergence_patience=2))


# ---------------------------------------------------------------------------
# Plan integrity validation
# ---------------------------------------------------------------------------


def _tiny_plan(st):
    return plan_blocks(st, 0, tile_i=256, blk=64, in_tiles=(256, 256))


def test_validate_plan_passes_good_plan(tiny_tensor):
    validate_plan(_tiny_plan(tiny_tensor))  # must not raise


def test_validate_plan_catches_corrupted_iloc(tiny_tensor):
    bad = faults.corrupt_plan(_tiny_plan(tiny_tensor))
    with pytest.raises(PlanValidationError, match="iloc"):
        validate_plan(bad)


def test_plans_validated_env_gate(monkeypatch):
    monkeypatch.delenv("REPRO_VALIDATE_PLANS", raising=False)
    assert not plans_validated()
    for v in ("1", "true", "YES", "on"):
        monkeypatch.setenv("REPRO_VALIDATE_PLANS", v)
        assert plans_validated()
    monkeypatch.setenv("REPRO_VALIDATE_PLANS", "0")
    assert not plans_validated()


def test_build_time_validation_accepts_real_plans(tiny_tensor, monkeypatch):
    monkeypatch.setenv("REPRO_VALIDATE_PLANS", "1")
    _tiny_plan(tiny_tensor)  # validated inside _assemble_plan; must not raise


def test_cache_hit_revalidates_resident_plan(tiny_tensor, monkeypatch):
    """REPRO_VALIDATE_PLANS=1 must catch a plan corrupted AFTER it entered
    the cache — the hit path revalidates, not just the build path."""
    ops.plan_cache_clear()
    args = ("mttkrp", tiny_tensor, 0, 8, None)
    op = ops._planned_cached(
        *args, lambda: ops.make_planned_mttkrp(tiny_tensor, 0, 8)
    )
    op.plan = faults.corrupt_plan(op.plan)  # corrupt the resident layout
    monkeypatch.setenv("REPRO_VALIDATE_PLANS", "1")
    with pytest.raises(PlanValidationError):
        ops._planned_cached(*args, lambda: pytest.fail("must be a cache hit"))
    ops.plan_cache_clear()


# ---------------------------------------------------------------------------
# Bounded plan cache
# ---------------------------------------------------------------------------


def test_plan_cache_config_rejects_nonpositive():
    with pytest.raises(ValueError):
        ops.plan_cache_config(0)


def test_plan_cache_churn_is_bounded(tiny_tensor):
    old = ops.plan_cache_config()
    ops.plan_cache_clear()
    try:
        ops.plan_cache_config(4)
        for mode in range(10):  # 10 distinct keys through a 4-entry cache
            ops._planned_cached(
                "mttkrp", tiny_tensor, mode, 8, None, lambda: object()
            )
        stats = ops.plan_cache_stats()
        assert stats["size"] <= 4
        assert stats["maxsize"] == 4
        assert stats["evictions"] >= 6
    finally:
        ops.plan_cache_config(old)
        ops.plan_cache_clear()


def test_plan_cache_config_evicts_down(tiny_tensor):
    old = ops.plan_cache_config()
    ops.plan_cache_clear()
    try:
        for mode in range(6):
            ops._planned_cached(
                "mttkrp", tiny_tensor, mode, 8, None, lambda: object()
            )
        ops.plan_cache_config(2)
        assert ops.plan_cache_stats()["size"] <= 2
    finally:
        ops.plan_cache_config(old)
        ops.plan_cache_clear()


# ---------------------------------------------------------------------------
# HBM admission control
# ---------------------------------------------------------------------------


def test_admission_bytes_report(tiny_tensor):
    ws = make_planned_cp_als(tiny_tensor, 8)
    rep = admission_bytes(ws)
    assert set(rep) == {"plan_bytes", "factor_bytes", "vmem_bytes",
                        "total_bytes"}
    assert rep["total_bytes"] == (
        rep["plan_bytes"] + rep["factor_bytes"] + rep["vmem_bytes"]
    )
    assert all(v > 0 for v in rep.values())


def test_admit_rejects_shrunk_budget(tiny_tensor):
    ws = make_planned_cp_als(tiny_tensor, 8)
    budget = faults.shrunk_budget(ws)
    with pytest.raises(AdmissionError) as ei:
        admit(ws, budget)
    assert ei.value.budget_bytes == budget
    admit(ws, admission_bytes(ws)["total_bytes"])  # exact fit admits


def test_ladder_steps_down_blk(tiny_tensor):
    """One byte under the default-blk footprint must admit at a smaller blk
    (smaller DMA blocks -> less per-group padding -> smaller plans)."""
    from repro.core.memctrl import MemoryControllerConfig

    build = lambda c: make_planned_cp_als(tiny_tensor, 8, cfg=c)
    top_blk = MemoryControllerConfig().dma.blk
    top_total = admission_bytes(build(None))["total_bytes"]
    ws, decision = plan_with_budget(build, top_total - 1)
    assert ws is not None
    assert decision["admitted"] == "pallas"
    assert decision["blk"] < top_blk
    assert len(decision["ladder"]) >= 2


def test_ladder_degrades_to_reference(tiny_tensor):
    """A budget below every pallas rung but above the raw-stream footprint
    routes decompose() to the reference method and still returns a state."""
    ref = reference_footprint_bytes(tiny_tensor, (8, 8, 8))
    budget = ref + 10_000  # far below the ~1.3 MB pallas rungs
    out = decompose(tiny_tensor, 8, iters=3, seed=0, hbm_budget=budget)
    want = decompose(tiny_tensor, 8, iters=3, seed=0, method="approach1")
    assert abs(out.fit_history[-1] - want.fit_history[-1]) < 1e-5


def test_impossible_budget_raises_with_ladder(tiny_tensor):
    with pytest.raises(AdmissionError) as ei:
        decompose(tiny_tensor, 8, iters=3, hbm_budget=1_000)
    assert ei.value.ladder  # every attempted rung is in the diagnostic
    assert ei.value.reference_bytes > 1_000


def test_budget_incompatible_with_auto_tune(tiny_tensor):
    with pytest.raises(ValueError, match="auto_tune"):
        decompose(tiny_tensor, 8, iters=2, hbm_budget=10**9, auto_tune=True)


# ---------------------------------------------------------------------------
# Checkpoint/resume: kill a sweep, resume bit-for-bit
# ---------------------------------------------------------------------------

_KILLED_SWEEP = """
import sys
sys.path.insert(0, {src!r})
from repro.api import decompose
from repro.core.coo import synthetic_tensor
from repro.testing import faults
{make_import}
st = synthetic_tensor((64, 48, 80), 2_000, seed=0, skew=0.8)
ws = {make_call}
faults.kill_at(ws, at_iter=3)
decompose(st, {rank}, format={format!r}, iters=5, seed=0, planned=ws,
          checkpoint_path={ckpt!r})
"""

_FORMAT_BUILDERS = {
    "cp": ("from repro.kernels.ops import make_planned_cp_als",
           "make_planned_cp_als(st, 8)", 8),
    "tucker": ("from repro.tucker.hooi import make_planned_tucker",
               "make_planned_tucker(st, (4, 4, 4))", (4, 4, 4)),
    "tt": ("from repro.tt.als import make_planned_tt",
           "make_planned_tt(st, (4, 3))", (4, 3)),
}


@pytest.mark.parametrize("format", ["cp", "tucker", "tt"])
def test_killed_sweep_resumes_to_clean_parity(tiny_tensor, tmp_path, format):
    """Kill the sweep dead (os._exit) before iteration 3, resume from the
    surviving checkpoints, and require the full fit history to match the
    uninterrupted run to 1e-6."""
    make_import, make_call, rank = _FORMAT_BUILDERS[format]
    code = _KILLED_SWEEP.format(
        src=os.path.join(ROOT, "src"), make_import=make_import,
        make_call=make_call, rank=rank, format=format, ckpt=str(tmp_path),
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=520, cwd=ROOT,
    )
    assert proc.returncode == 17, (
        f"expected the kill_at exit code, got {proc.returncode}\n"
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    )
    assert os.listdir(str(tmp_path)), "no checkpoint survived the kill"

    resumed = decompose(tiny_tensor, rank, format=format, iters=ITERS,
                        seed=0, checkpoint_path=str(tmp_path))
    clean = decompose(tiny_tensor, rank, format=format, iters=ITERS, seed=0)
    assert len(resumed.fit_history) == len(clean.fit_history)
    deltas = [abs(a - b)
              for a, b in zip(resumed.fit_history, clean.fit_history)]
    assert max(deltas) < 1e-6, deltas


def test_resume_rejects_mismatched_shapes(tiny_tensor, tmp_path):
    """A checkpoint from a different rank must fail loudly, not silently
    corrupt the resumed run."""
    decompose(tiny_tensor, 8, iters=2, seed=0, checkpoint_path=str(tmp_path))
    with pytest.raises(ValueError, match="checkpoint"):
        decompose(tiny_tensor, 4, iters=4, seed=0,
                  checkpoint_path=str(tmp_path))


def test_checkpoint_every_cadence(tiny_tensor, tmp_path):
    """checkpoint_every=2 writes at iterations 1, 3 and at the final stop."""
    from repro.train.checkpoint import CheckpointManager

    decompose(tiny_tensor, 8, iters=5, seed=0, checkpoint_path=str(tmp_path),
              checkpoint_every=2)
    steps = CheckpointManager(str(tmp_path), keep=2).all_steps()
    assert steps and steps[-1] == 4
