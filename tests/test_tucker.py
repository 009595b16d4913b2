"""Sparse Tucker (HOOI) on the memory controller: TTMc kernel/oracle parity,
pallas-vs-reference HOOI fit match, plan amortization, and the kind-keyed
shared plan cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.kernels.ops as ops_mod
from repro.core.coo import SparseTensor, frostt_like, random_factors, synthetic_tensor
from repro.core.memctrl import CacheEngineConfig, DMAEngineConfig, MemoryControllerConfig
from repro.kernels.mttkrp_pallas import pad_factor, rank_padded
from repro.kernels.ops import (
    make_planned_ttmc,
    mttkrp_auto,
    plan_cache_clear,
    plan_cache_stats,
    tucker_auto,
)
from repro.kernels.ref import ttmc_plan_ref, ttmc_ref, ttmc_ref_dense
from repro.kernels.ttm_pallas import kron_cols
from repro.tucker import init_tucker_factors, make_planned_tucker, tucker_hooi


def low_multilinear_rank_tensor(shape=(10, 9, 8), ranks=(2, 3, 2), seed=0) -> SparseTensor:
    """Exactly-low-multilinear-rank tensor with FULL support in COO form (the
    implicit zeros are fitted too, so the recovery test needs every entry)."""
    rng = np.random.default_rng(seed)
    core = rng.standard_normal(ranks)
    us = [np.linalg.qr(rng.standard_normal((s, r)))[0] for s, r in zip(shape, ranks)]
    dense = np.einsum("abc,ia,jb,kc->ijk", core, *us)
    grids = np.meshgrid(*[np.arange(s) for s in shape], indexing="ij")
    idx = np.stack([g.ravel() for g in grids], axis=1).astype(np.int32)
    return SparseTensor(idx, dense.ravel().astype(np.float32), shape)


# ---------------------------------------------------------------------------
# TTMc oracle + kernel
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    nnz=st.integers(1, 200),
    base=st.tuples(st.integers(4, 20), st.integers(4, 20), st.integers(4, 20)),
    extra=st.sampled_from([(), (7,), (7, 6)]),
    mode=st.integers(0, 2),
    rank=st.integers(1, 4),
    seed=st.integers(0, 99),
)
def test_ttmc_ref_matches_dense_einsum(nnz, base, extra, mode, rank, seed):
    """Property (stub-compatible): the sparse gather/Kronecker/segment_sum
    TTMc oracle equals a dense np.einsum contraction on 3/4/5-mode tensors,
    for every output mode and rank combination drawn."""
    dims = base + extra
    st_t = synthetic_tensor(dims, nnz, seed=seed, skew=0.5)
    rng = np.random.default_rng(seed + 1)
    facs = [rng.standard_normal((s, rank)).astype(np.float32) for s in dims]
    out = ttmc_ref(
        jnp.asarray(st_t.indices),
        jnp.asarray(st_t.values),
        [jnp.asarray(f) for f in facs],
        mode,
        st_t.shape[mode],
    )
    ref = ttmc_ref_dense(st_t.indices, st_t.values, facs, mode, st_t.shape[mode])
    assert out.shape == (st_t.shape[mode], kron_cols([rank] * (len(dims) - 1)))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_ttmc_pallas_all_modes(tiny_tensor, mode):
    """The planned Pallas TTMc kernel (interpret mode) == the jnp oracle on
    every output mode of the shared BlockPlan layout."""
    facs = random_factors(jax.random.PRNGKey(0), tiny_tensor.shape, 4)
    out = tucker_auto(tiny_tensor, facs, mode, method="pallas")
    ref = tucker_auto(tiny_tensor, facs, mode, method="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ttmc_pallas_mixed_ranks(tiny_tensor):
    """Input factors with DIFFERENT ranks (the Tucker-specific case MTTKRP
    never exercises): per-factor lane padding + row-major Kronecker order."""
    rng = jax.random.PRNGKey(3)
    ranks = (3, 5, 2)
    facs = [
        jax.random.normal(k, (s, r))
        for k, s, r in zip(jax.random.split(rng, 3), tiny_tensor.shape, ranks)
    ]
    for mode in range(3):
        out = tucker_auto(tiny_tensor, facs, mode, method="pallas")
        ref = tucker_auto(tiny_tensor, facs, mode, method="reference")
        assert out.shape[1] == kron_cols([r for m, r in enumerate(ranks) if m != mode])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fixture", ["tensor4d", "tensor5d"])
def test_ttmc_pallas_vs_plan_ref_higher_order(request, fixture):
    """N-mode TTMc kernel vs the layout-level oracle, including padded rows."""
    st_t = request.getfixturevalue(fixture)
    mode = 1
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
        dma=DMAEngineConfig(blk=32),
    )
    op = make_planned_ttmc(st_t, mode, (3,) * st_t.nmodes, cfg=cfg)
    plan = op.plan
    facs = random_factors(jax.random.PRNGKey(6), st_t.shape, 3)
    pads = tuple(
        pad_factor(facs[m], rows, rank_padded(3))
        for m, rows in zip(plan.in_modes, plan.in_rows)
    )
    ref = ttmc_plan_ref(plan, pads, op.in_ranks)
    out = op.output(facs, st_t.shape[mode])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref)[: st_t.shape[mode]], rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# HOOI loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("source", ["tiny", "tensor4d", "tensor5d"])
def test_hooi_pallas_matches_reference(request, source):
    """Acceptance: tucker_hooi(method='pallas') — the PlannedTucker workspace
    on the TTM-chain kernel — and the pure-jnp reference drive matching fit
    histories on 3-, 4- and 5-mode tensors."""
    st_t = frostt_like("tiny") if source == "tiny" else request.getfixturevalue(source)
    ranks = (3,) * st_t.nmodes
    s_p = tucker_hooi(st_t, ranks, iters=3, method="pallas", seed=0)
    s_r = tucker_hooi(st_t, ranks, iters=3, method="reference", seed=0)
    np.testing.assert_allclose(s_p.fit_history, s_r.fit_history, atol=1e-4)
    assert s_p.core.shape == ranks


def test_hooi_jitted_sweep_matches_eager():
    """The jitted HOOI sweep (rank-padded, device-resident factors, one
    compiled function per iteration) reproduces the eager per-mode pallas
    dispatch loop."""
    st_t = frostt_like("tiny")
    s_jit = tucker_hooi(st_t, (4, 4, 4), iters=3, method="pallas", seed=0)
    s_eag = tucker_hooi(st_t, (4, 4, 4), iters=3, method="pallas", seed=0, jit_sweep=False)
    np.testing.assert_allclose(s_jit.fit_history, s_eag.fit_history, atol=1e-5)
    for fj, fe in zip(s_jit.factors, s_eag.factors):
        assert fj.shape == fe.shape  # sliced back to true (I_m, R_m)
        np.testing.assert_allclose(np.asarray(fj), np.asarray(fe), atol=1e-4)


def test_hooi_recovers_low_multilinear_rank():
    """Exact recovery: a full-support tensor with multilinear rank (2,3,2)
    is recovered to fit ~ 1 at the matching core ranks."""
    st_t = low_multilinear_rank_tensor()
    state = tucker_hooi(st_t, (2, 3, 2), iters=8, method="reference", seed=1)
    assert state.fit_history[-1] > 0.999, state.fit_history
    # HOOI can hit fit ~= 1 on the first sweep; later iterations may wobble
    # by float32 rounding, so only pin against a real regression.
    assert state.fit_history[-1] >= state.fit_history[0] - 1e-3


def test_hooi_factors_orthonormal_and_fit_formula(tiny_tensor):
    """HOOI invariants: factors keep orthonormal columns, and the core-based
    fit equals the explicit reconstruction residual on the non-zero support
    + implicit zeros (checked densely on the tiny shape)."""
    ranks = (4, 4, 4)
    state = tucker_hooi(tiny_tensor, ranks, iters=2, method="pallas", seed=0)
    for f in state.factors:
        np.testing.assert_allclose(
            np.asarray(f.T @ f), np.eye(f.shape[1]), atol=1e-4
        )
    dense = np.zeros(tiny_tensor.shape, np.float64)
    np.add.at(
        dense,
        tuple(tiny_tensor.indices[:, m] for m in range(3)),
        tiny_tensor.values.astype(np.float64),
    )
    us = [np.asarray(f, np.float64) for f in state.factors]
    recon = np.einsum("abc,ia,jb,kc->ijk", np.asarray(state.core, np.float64), *us)
    fit_dense = 1.0 - np.linalg.norm(dense - recon) / np.linalg.norm(dense)
    assert abs(fit_dense - state.fit_history[-1]) < 1e-3


def test_hooi_tol_early_exit():
    st_t = low_multilinear_rank_tensor(seed=3)
    state = tucker_hooi(st_t, (2, 3, 2), iters=40, tol=1e-6, method="reference", seed=1)
    assert len(state.fit_history) < 40
    assert state.fit_history[-1] > 0.99


def test_hooi_validates_core_ranks(tiny_tensor):
    with pytest.raises(ValueError, match="entries"):
        tucker_hooi(tiny_tensor, (4, 4), iters=1)
    with pytest.raises(ValueError, match="out of range"):
        tucker_hooi(tiny_tensor, (0, 4, 4), iters=1)
    with pytest.raises(ValueError, match="out of range"):
        tucker_hooi(tiny_tensor, (4, 4, 1000), iters=1)
    with pytest.raises(ValueError, match="full row rank"):
        # 9 > 2*2: the mode-0 unfolding of the core would be rank-deficient
        tucker_hooi(tiny_tensor, (9, 2, 2), iters=1)
    ws = make_planned_tucker(tiny_tensor, (4, 4, 4))
    with pytest.raises(ValueError, match="workspace"):
        tucker_hooi(tiny_tensor, (3, 3, 3), iters=1, method="pallas", planned=ws)
    with pytest.raises(ValueError, match="ignored"):
        tucker_hooi(tiny_tensor, (4, 4, 4), iters=1, method="reference", planned=ws)


# ---------------------------------------------------------------------------
# Plan amortization + shared kind-keyed plan cache
# ---------------------------------------------------------------------------


def test_planned_tucker_plans_built_once(monkeypatch):
    """Acceptance (plan amortization): plan_blocks runs exactly once per
    output mode across ALL HOOI iterations, and a prebuilt workspace skips
    planning entirely."""
    calls = []
    orig = ops_mod.plan_blocks

    def counting(*a, **k):
        calls.append(a)
        return orig(*a, **k)

    monkeypatch.setattr(ops_mod, "plan_blocks", counting)
    st_t = frostt_like("tiny")
    tucker_hooi(st_t, (4, 4, 4), iters=4, method="pallas", seed=0)
    assert len(calls) == st_t.nmodes

    planned = make_planned_tucker(st_t, (4, 4, 4))
    calls.clear()
    s = tucker_hooi(st_t, (4, 4, 4), iters=2, method="pallas", planned=planned, seed=0)
    assert calls == []
    assert len(s.fit_history) == 2


def test_planned_tucker_plan_bytes_and_padded_rows(tiny_tensor):
    ws = make_planned_tucker(tiny_tensor, (4, 4, 4))
    assert ws.plan_bytes() > 0
    prows = ws.padded_rows
    assert all(
        pr >= s and pr >= ws.ops[m].plan.out_rows
        for m, (pr, s) in enumerate(zip(prows, tiny_tensor.shape))
    )
    assert ws.rank_pads == (128, 128, 128)


def test_tucker_auto_cache_hits(tiny_tensor):
    """Acceptance: repeated tucker_auto calls are served from the shared plan
    cache (nonzero hits), tracked under the 'ttmc' kind."""
    plan_cache_clear()
    facs = random_factors(jax.random.PRNGKey(1), tiny_tensor.shape, 4)
    out1 = tucker_auto(tiny_tensor, facs, 0, method="pallas")
    out2 = tucker_auto(tiny_tensor, facs, 0, method="pallas")
    s = plan_cache_stats()
    assert s["by_kind"]["ttmc"] == {"hits": 1, "misses": 1}
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    tucker_auto(tiny_tensor, facs, 1, method="pallas")  # new mode -> miss
    assert plan_cache_stats()["by_kind"]["ttmc"] == {"hits": 1, "misses": 2}
    plan_cache_clear()


def test_plan_cache_no_cross_kind_collisions(tiny_tensor):
    """Acceptance (the latent collision the kind field fixes): MTTKRP and
    TTMc calls sharing tensor fingerprint + mode + an identical-looking rank
    key must never serve each other's plans."""
    plan_cache_clear()
    rank = 4
    facs = random_factors(jax.random.PRNGKey(1), tiny_tensor.shape, rank)
    mttkrp_auto(tiny_tensor, facs, 0, method="pallas")
    tucker_auto(tiny_tensor, facs, 0, method="pallas")
    s = plan_cache_stats()
    # both kinds missed: the second call did NOT hit the first kind's entry
    assert s["by_kind"]["mttkrp"] == {"hits": 0, "misses": 1}
    assert s["by_kind"]["ttmc"] == {"hits": 0, "misses": 1}
    assert s == {
        "hits": 0,
        "misses": 2,
        "evictions": 0,
        "size": 2,
        "maxsize": s["maxsize"],  # env-configurable (REPRO_PLAN_CACHE_MAX)
        "by_kind": {
            "mttkrp": {"hits": 0, "misses": 1},
            "ttmc": {"hits": 0, "misses": 1},
            "tt": {"hits": 0, "misses": 0},
        },
    }
    # and each kind still hits itself afterwards
    mttkrp_auto(tiny_tensor, facs, 0, method="pallas")
    tucker_auto(tiny_tensor, facs, 0, method="pallas")
    s = plan_cache_stats()
    assert s["by_kind"]["mttkrp"]["hits"] == 1
    assert s["by_kind"]["ttmc"]["hits"] == 1
    plan_cache_clear()


def test_plan_cache_tt_kind_isolated(tiny_tensor):
    """Regression: a 'tt' plan for the same (tensor, mode) never collides
    with the 'mttkrp' or 'ttmc' entries, and vice versa — the TT kernel
    instance carries interface-pair state the other kernels must never
    see."""
    from repro.tt import init_tt_cores, tt_auto

    plan_cache_clear()
    rank = 4
    facs = random_factors(jax.random.PRNGKey(1), tiny_tensor.shape, rank)
    cores = init_tt_cores(jax.random.PRNGKey(2), tiny_tensor.shape, (4, 4))
    mttkrp_auto(tiny_tensor, facs, 0, method="pallas")
    tucker_auto(tiny_tensor, facs, 0, method="pallas")
    tt_auto(tiny_tensor, cores, 0, method="pallas")
    s = plan_cache_stats()
    # three kinds, three misses: nobody served anybody else's plan
    assert s == {
        "hits": 0,
        "misses": 3,
        "evictions": 0,
        "size": 3,
        "maxsize": s["maxsize"],  # env-configurable (REPRO_PLAN_CACHE_MAX)
        "by_kind": {
            "mttkrp": {"hits": 0, "misses": 1},
            "ttmc": {"hits": 0, "misses": 1},
            "tt": {"hits": 0, "misses": 1},
        },
    }
    # tt hits itself afterwards, without disturbing the other kinds
    tt_auto(tiny_tensor, cores, 0, method="pallas")
    s = plan_cache_stats()
    assert s["by_kind"]["tt"] == {"hits": 1, "misses": 1}
    assert s["by_kind"]["mttkrp"] == {"hits": 0, "misses": 1}
    assert s["by_kind"]["ttmc"] == {"hits": 0, "misses": 1}
    plan_cache_clear()


def test_tucker_auto_rejects_unknown_method(tiny_tensor):
    facs = random_factors(jax.random.PRNGKey(0), tiny_tensor.shape, 4)
    with pytest.raises(ValueError, match="method"):
        tucker_auto(tiny_tensor, facs, 0, method="approach1")


def test_init_tucker_factors_orthonormal():
    facs = init_tucker_factors(jax.random.PRNGKey(5), (30, 20, 25), (4, 6, 5))
    for f, (s, r) in zip(facs, [(30, 4), (20, 6), (25, 5)]):
        assert f.shape == (s, r)
        np.testing.assert_allclose(np.asarray(f.T @ f), np.eye(r), atol=1e-5)
