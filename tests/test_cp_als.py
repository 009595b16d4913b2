"""CP-ALS (paper Alg. 1) end-to-end: convergence, method/layout equivalence,
and the Pallas-kernel-backed path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ops as ops_mod
from repro.core.coo import SparseTensor, frostt_like, synthetic_tensor
from repro.core.cp_als import _normalize, cp_als, fit_value, gram_hadamard
from repro.kernels.ops import make_planned_cp_als, make_planned_mttkrp


def low_rank_tensor(shape=(20, 15, 18), rank=4, seed=0) -> SparseTensor:
    """Exactly-low-rank tensor with FULL support in COO form.  (Sampling a
    low-rank tensor at sparse coordinates does NOT give a low-rank sparse
    tensor — CP-ALS fits the implicit zeros too — so the recovery test needs
    every entry present.)"""
    rng = np.random.default_rng(seed)
    facs = [rng.standard_normal((s, rank)) for s in shape]
    grids = np.meshgrid(*[np.arange(s) for s in shape], indexing="ij")
    idx = np.stack([g.ravel() for g in grids], axis=1).astype(np.int32)
    vals = np.einsum("zr,zr,zr->z", facs[0][idx[:, 0]], facs[1][idx[:, 1]], facs[2][idx[:, 2]])
    return SparseTensor(idx, vals.astype(np.float32), shape)


def test_fit_improves_and_converges():
    """Exact recovery of a rank-4 tensor (decomposed at rank 5: ALS at the
    exact rank can stall in the classic swamp; slight over-parameterization
    is the standard fix and recovers fit = 1)."""
    st_t = low_rank_tensor()
    state = cp_als(st_t, rank=5, iters=25, seed=2)
    fits = state.fit_history
    assert fits[-1] > 0.95, fits
    assert fits[-1] >= fits[0]


def test_methods_agree():
    """Approach 1 and Approach 2 drive identical ALS trajectories (same
    math, different memory schedule — the paper's central claim)."""
    st_t = low_rank_tensor(seed=3)
    s1 = cp_als(st_t, rank=4, iters=5, method="approach1", seed=0)
    s2 = cp_als(st_t, rank=4, iters=5, method="approach2", seed=0)
    np.testing.assert_allclose(s1.fit_history, s2.fit_history, rtol=1e-4, atol=1e-5)
    for f1, f2 in zip(s1.factors, s2.factors):
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-3, atol=1e-4)


def test_layouts_agree():
    """'remap' (single stream re-sorted per mode, Alg. 5) == 'copies'
    (per-mode sorted copies) — the trade the paper discusses in Sec. 3."""
    st_t = low_rank_tensor(seed=4)
    s1 = cp_als(st_t, rank=3, iters=4, layout="remap", seed=0)
    s2 = cp_als(st_t, rank=3, iters=4, layout="copies", seed=0)
    np.testing.assert_allclose(s1.fit_history, s2.fit_history, rtol=1e-4, atol=1e-5)


def test_pallas_backed_cp_als():
    """CP-ALS with the Pallas kernel (interpret mode) as the MTTKRP engine."""
    st_t = low_rank_tensor(shape=(16, 12, 20), seed=5)

    ops = {m: make_planned_mttkrp(st_t.sorted_by(m), m, 4) for m in range(3)}

    def mttkrp_fn(indices, values, factors, mode, out_rows):
        return ops[mode].output(factors, out_rows)

    s_k = cp_als(st_t, rank=4, iters=5, layout="copies", mttkrp_fn=mttkrp_fn, seed=0)
    s_j = cp_als(st_t, rank=4, iters=5, layout="copies", seed=0)
    np.testing.assert_allclose(s_k.fit_history, s_j.fit_history, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("source", ["tiny", "tensor4d", "tensor5d"])
def test_planned_cp_als_matches_pure_jax(request, source):
    """Acceptance: cp_als(method='pallas') — the PlannedCPALS workspace — and
    pure-JAX approach1 drive matching fit histories on 3-, 4- and 5-mode
    tensors (the whole ALS loop runs on the memory controller)."""
    st_t = frostt_like("tiny") if source == "tiny" else request.getfixturevalue(source)
    s_p = cp_als(st_t, rank=4, iters=3, method="pallas", seed=0)
    s_1 = cp_als(st_t, rank=4, iters=3, method="approach1", layout="copies", seed=0)
    np.testing.assert_allclose(s_p.fit_history, s_1.fit_history, atol=1e-4)


def test_planned_cp_als_plans_built_once(monkeypatch):
    """Plan amortization (paper: layout generation is per-mode, not
    per-iteration): plan_blocks runs exactly once per output mode regardless
    of the iteration count, and a prebuilt workspace skips planning
    entirely."""
    calls = []
    orig = ops_mod.plan_blocks

    def counting(*a, **k):
        calls.append(a)
        return orig(*a, **k)

    monkeypatch.setattr(ops_mod, "plan_blocks", counting)
    st_t = frostt_like("tiny")
    cp_als(st_t, rank=4, iters=4, method="pallas", seed=0)
    assert len(calls) == st_t.nmodes

    planned = make_planned_cp_als(st_t, 4)
    calls.clear()
    s = cp_als(st_t, rank=4, iters=2, method="pallas", planned=planned, seed=0)
    assert calls == []
    assert len(s.fit_history) == 2


@pytest.mark.parametrize("source", ["tiny", "tensor4d", "tensor5d"])
def test_jitted_sweep_matches_eager_pallas(request, source):
    """Acceptance: the jitted ALS sweep (rank-padded, device-resident factors,
    one compiled function per iteration) reproduces the eager per-mode pallas
    dispatch loop to 1e-5 on 3/4/5-mode tensors."""
    st_t = frostt_like("tiny") if source == "tiny" else request.getfixturevalue(source)
    s_jit = cp_als(st_t, rank=4, iters=3, method="pallas", seed=0)
    s_eag = cp_als(st_t, rank=4, iters=3, method="pallas", seed=0, jit_sweep=False)
    np.testing.assert_allclose(s_jit.fit_history, s_eag.fit_history, atol=1e-5)
    for fj, fe in zip(s_jit.factors, s_eag.factors):
        assert fj.shape == fe.shape  # sliced back to true (I_m, R)
        np.testing.assert_allclose(np.asarray(fj), np.asarray(fe), atol=1e-4)


@pytest.mark.parametrize("layout", ["copies", "remap"])
def test_jitted_sweep_matches_eager_pure_jax(layout):
    """The pure-JAX layouts get the same treatment: one jitted sweep per
    iteration must match the eager dispatch loop."""
    st_t = low_rank_tensor(seed=6)
    s_jit = cp_als(st_t, rank=3, iters=4, layout=layout, seed=0)
    s_eag = cp_als(st_t, rank=3, iters=4, layout=layout, seed=0, jit_sweep=False)
    np.testing.assert_allclose(s_jit.fit_history, s_eag.fit_history, atol=1e-5)


def test_planned_cp_als_pads_once_per_mode(monkeypatch):
    """Regression (fast-path contract): a full cp_als(method='pallas') run
    pads each factor exactly once — in the shared PlannedWorkspace.pad_factors
    (kernels/workspace.py) — instead of N x iters eager pad_factor calls;
    iterations update factors in padded space."""
    import repro.kernels.workspace as workspace_mod

    calls = []
    orig = workspace_mod.pad_factor

    def counting(*a, **k):
        calls.append(a)
        return orig(*a, **k)

    monkeypatch.setattr(workspace_mod, "pad_factor", counting)
    st_t = frostt_like("tiny")
    cp_als(st_t, rank=4, iters=3, method="pallas", seed=0)
    assert len(calls) == st_t.nmodes


def test_cp_als_tol_early_exit_jitted():
    """tol moved to a host check on the per-iteration fit scalar: the loop
    must stop once successive fits are within tol, in fewer than `iters`
    iterations on an exactly-recoverable tensor."""
    st_t = low_rank_tensor(seed=8)
    state = cp_als(st_t, rank=5, iters=40, tol=1e-6, seed=2)
    assert len(state.fit_history) < 40
    assert state.fit_history[-1] > 0.9


def test_cp_als_rejects_unknown_layout():
    """'planned' is an internal sentinel of the pallas path: reaching it via
    the public `layout` arg would feed an unsorted stream to approach1 with
    its sorted_by_mode=True promise, so it must be rejected up front."""
    st_t = frostt_like("tiny")
    with pytest.raises(ValueError, match="layout"):
        cp_als(st_t, rank=4, iters=1, layout="planned")


def test_normalize_first_iteration_convention():
    """Regression: _normalize must apply the documented first-iteration
    max(norm, 1) convention (it used to ignore `it` entirely) — sub-unit
    columns are left unscaled on iteration 0, divided exactly afterwards."""
    f = jnp.array([[0.3, 3.0], [0.4, 4.0]], jnp.float32)  # col norms 0.5, 5.0
    f0, n0 = _normalize(f, 0)
    np.testing.assert_allclose(np.asarray(n0), [1.0, 5.0], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(f0[:, 0]), [0.3, 0.4], rtol=1e-6)
    f1, n1 = _normalize(f, 1)
    np.testing.assert_allclose(np.asarray(n1), [0.5, 5.0], rtol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(f1), axis=0), [1.0, 1.0], rtol=1e-6
    )


def test_poorly_scaled_fit_trajectory():
    """Fit-trajectory regression for the max(norm,1) convention: on a badly
    down-scaled tensor (tiny first-iteration column norms) the trajectory
    stays finite and still recovers the decomposition."""
    base = low_rank_tensor(seed=7)
    scaled = SparseTensor(base.indices, base.values * 1e-4, base.shape)
    state = cp_als(scaled, rank=5, iters=25, seed=2)
    fits = np.array(state.fit_history)
    assert np.all(np.isfinite(fits))
    assert fits[-1] > 0.95, fits
    assert all(np.isfinite(np.asarray(f)).all() for f in state.factors)


def test_gram_hadamard():
    key = jax.random.PRNGKey(0)
    facs = [jax.random.normal(k, (10, 4)) for k in jax.random.split(key, 3)]
    g = gram_hadamard(facs, 0)
    want = (facs[1].T @ facs[1]) * (facs[2].T @ facs[2])
    np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=1e-5)


def test_higher_order_cp_als(tensor4d):
    state = cp_als(tensor4d, rank=3, iters=3, seed=0)
    assert len(state.factors) == 4
    assert all(np.isfinite(f).all() for f in map(np.asarray, state.factors))
