"""Tensor-train (repro.tt) on the memory controller: TT-core kernel/oracle
parity, TT-SVD init, pallas-vs-reference TT-ALS fit match on 3/4/5-mode
tensors, exact low-TT-rank recovery, workspace validation contracts, and the
2-device sharded parity subprocess."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

from repro.core.coo import SparseTensor, synthetic_tensor
from repro.core.memctrl import CacheEngineConfig, DMAEngineConfig, MemoryControllerConfig
from repro.kernels.mttkrp_pallas import pad_factor, rank_padded
from repro.kernels.ops import make_planned_ttcore, tt_auto
from repro.kernels.ref import ttcore_plan_ref, ttcore_ref, ttcore_ref_dense
from repro.tt import (
    PlannedTT,
    TTState,
    core_to_matrix,
    init_tt_cores,
    make_planned_tt,
    tt_als,
    tt_svd,
)
from repro.tt.als import _TT_SVD_DENSE_LIMIT, _validated_tt_ranks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL_CFG = MemoryControllerConfig(
    cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
    dma=DMAEngineConfig(blk=32),
)


def _bond_pairs(tt_ranks, nmodes):
    bounds = (1,) + tuple(tt_ranks) + (1,)
    return [(bounds[k], bounds[k + 1]) for k in range(nmodes)]


def random_cores(shape, tt_ranks, seed=0):
    rng = np.random.default_rng(seed)
    return [
        jnp.asarray(rng.standard_normal((rl, s, rr)), jnp.float32)
        for s, (rl, rr) in zip(shape, _bond_pairs(tt_ranks, len(shape)))
    ]


def low_tt_rank_tensor(shape=(9, 8, 7), tt_ranks=(2, 3), seed=0) -> SparseTensor:
    """Exactly-low-TT-rank tensor with FULL support in COO form (the implicit
    zeros are fitted too, so the recovery test needs every entry)."""
    cores = random_cores(shape, tt_ranks, seed=seed)
    dense = np.asarray(TTState(cores=cores, fit_history=[]).full(), np.float64)
    grids = np.meshgrid(*[np.arange(s) for s in shape], indexing="ij")
    idx = np.stack([g.ravel() for g in grids], axis=1).astype(np.int32)
    return SparseTensor(idx, dense.ravel().astype(np.float32), shape)


# ---------------------------------------------------------------------------
# TT-core oracle + kernel
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    nnz=hst.integers(1, 200),
    base=hst.tuples(hst.integers(4, 16), hst.integers(4, 16), hst.integers(4, 16)),
    extra=hst.sampled_from([(), (7,), (7, 6)]),
    mode_pick=hst.integers(0, 4),
    rank=hst.integers(1, 3),
    seed=hst.integers(0, 99),
)
def test_ttcore_ref_matches_dense_einsum(nnz, base, extra, mode_pick, rank, seed):
    """Property (stub-compatible): the sparse gather/interface-chain TT-core
    oracle equals the densify-and-einsum cross-check on 3/4/5-mode tensors,
    for every output mode and interior bond rank drawn."""
    dims = base + extra
    mode = mode_pick % len(dims)
    st_t = synthetic_tensor(dims, nnz, seed=seed, skew=0.5)
    cores = random_cores(dims, (rank,) * (len(dims) - 1), seed=seed + 1)
    out = ttcore_ref(
        jnp.asarray(st_t.indices),
        jnp.asarray(st_t.values),
        cores,
        mode,
        st_t.shape[mode],
    )
    ref = ttcore_ref_dense(
        st_t.indices,
        st_t.values,
        [np.asarray(c) for c in cores],
        mode,
        st_t.shape[mode],
    )
    rl, rr = _bond_pairs((rank,) * (len(dims) - 1), len(dims))[mode]
    assert out.shape == (st_t.shape[mode], rl * rr)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_ttcore_pallas_all_modes(tiny_tensor, mode):
    """The planned Pallas TT-core kernel (interpret mode) == the jnp oracle
    on every output mode, asymmetric bond ranks to catch (rl, rr) swaps."""
    tt_ranks = (3, 5)
    cores = random_cores(tiny_tensor.shape, tt_ranks, seed=7)
    op = make_planned_ttcore(
        tiny_tensor, mode, tt_ranks, cfg=SMALL_CFG
    )
    mats = [core_to_matrix(c) for c in cores]
    out = op.output(mats, tiny_tensor.shape[mode])
    ref = ttcore_ref(
        jnp.asarray(tiny_tensor.indices),
        jnp.asarray(tiny_tensor.values),
        cores,
        mode,
        tiny_tensor.shape[mode],
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ttcore_pallas_4d(tensor4d):
    """N-mode kernel: three chained input interfaces on a 4-mode tensor."""
    tt_ranks = (2, 4, 3)
    cores = random_cores(tensor4d.shape, tt_ranks, seed=9)
    for mode in (0, 2, 3):
        op = make_planned_ttcore(
            tensor4d, mode, tt_ranks, cfg=SMALL_CFG
        )
        out = op.output([core_to_matrix(c) for c in cores], tensor4d.shape[mode])
        ref = ttcore_ref(
            jnp.asarray(tensor4d.indices),
            jnp.asarray(tensor4d.values),
            cores,
            mode,
            tensor4d.shape[mode],
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
        )


def test_ttcore_plan_ref_matches_pallas(tiny_tensor):
    """The BlockPlan-layout oracle reproduces the Pallas output bit-exactly
    in padded space (same gather order, same segment reduction)."""
    tt_ranks = (4, 3)
    cores = random_cores(tiny_tensor.shape, tt_ranks, seed=3)
    op = make_planned_ttcore(tiny_tensor, 1, tt_ranks, cfg=SMALL_CFG)
    p = op.plan
    pads = tuple(
        pad_factor(core_to_matrix(cores[im]), rows, rank_padded(a * b))
        for im, rows, (a, b) in zip(p.in_modes, p.in_rows, op.in_rank_pairs)
    )
    out = op.call_padded(pads)
    ref = ttcore_plan_ref(p, pads, op.in_rank_pairs, op.n_left)
    np.testing.assert_allclose(
        np.asarray(out[:, : op.out_cols]),
        np.asarray(ref[:, : op.out_cols]),
        rtol=1e-5,
        atol=1e-5,
    )


def test_tt_auto_pallas_matches_reference(tiny_tensor):
    """The one-shot dispatcher: pallas == reference for every output mode."""
    cores = random_cores(tiny_tensor.shape, (3, 4), seed=5)
    for mode in range(3):
        out = tt_auto(tiny_tensor, cores, mode, method="pallas", cfg=SMALL_CFG)
        ref = tt_auto(tiny_tensor, cores, mode, method="reference")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
        )
    with pytest.raises(ValueError, match="expected 'pallas' or 'reference'"):
        tt_auto(tiny_tensor, cores, 0, method="einsum")


# ---------------------------------------------------------------------------
# TT-SVD init
# ---------------------------------------------------------------------------


def test_tt_svd_exact_at_true_ranks():
    """TT-SVD at the generating bond ranks reconstructs the tensor exactly
    (the unfolding ranks are <= the requested bonds, so no truncation)."""
    st = low_tt_rank_tensor(shape=(9, 8, 7), tt_ranks=(2, 3), seed=1)
    cores = tt_svd(st, (2, 3))
    dense = np.zeros(st.shape, np.float64)
    dense[tuple(st.indices[:, m] for m in range(3))] = st.values
    full = np.asarray(TTState(cores=cores, fit_history=[]).full(), np.float64)
    np.testing.assert_allclose(full, dense, rtol=1e-4, atol=1e-4)


def test_tt_svd_pads_rank_deficient_bonds():
    """Requesting bonds above the unfolding rank zero-pads the cores instead
    of failing — the shapes honour the request, the reconstruction is still
    exact."""
    st = low_tt_rank_tensor(shape=(8, 7, 6), tt_ranks=(2, 2), seed=2)
    cores = tt_svd(st, (5, 5))
    assert [c.shape for c in cores] == [(1, 8, 5), (5, 7, 5), (5, 6, 1)]
    dense = np.zeros(st.shape, np.float64)
    dense[tuple(st.indices[:, m] for m in range(3))] = st.values
    full = np.asarray(TTState(cores=cores, fit_history=[]).full(), np.float64)
    np.testing.assert_allclose(full, dense, rtol=1e-4, atol=1e-4)


def test_tt_svd_dense_guard(small_tensor):
    """prod(shape) past the densification guard is rejected with the
    init='random' hint, and init='auto' silently takes the random path."""
    assert np.prod(small_tensor.shape) > _TT_SVD_DENSE_LIMIT
    with pytest.raises(ValueError, match="use init='random'"):
        tt_svd(small_tensor, (2, 2))
    # init='auto' must not densify: just resolving the init path should work.
    state = tt_als(small_tensor, 2, iters=1, method="reference", init="auto")
    assert len(state.fit_history) == 1


def test_init_tt_cores_left_orthogonal():
    cores = init_tt_cores(jax.random.PRNGKey(0), (10, 9, 8), (3, 4))
    assert [c.shape for c in cores] == [(1, 10, 3), (3, 9, 4), (4, 8, 1)]
    for c in cores[:-1]:
        m = np.asarray(c.reshape(c.shape[0] * c.shape[1], c.shape[2]))
        np.testing.assert_allclose(m.T @ m, np.eye(m.shape[1]), atol=1e-5)


# ---------------------------------------------------------------------------
# rank validation
# ---------------------------------------------------------------------------


def test_validated_tt_ranks_contracts(tiny_tensor):
    assert _validated_tt_ranks(tiny_tensor, 4) == (4, 4)
    assert _validated_tt_ranks(tiny_tensor, (2, 5)) == (2, 5)
    with pytest.raises(ValueError, match="3 entries for a 3-mode tensor"):
        _validated_tt_ranks(tiny_tensor, (2, 2, 2))
    with pytest.raises(ValueError, match="out of range"):
        _validated_tt_ranks(tiny_tensor, (0, 2))
    with pytest.raises(ValueError, match="out of range"):
        # bond 0's bound is min(64, 48*80) = 64
        _validated_tt_ranks(tiny_tensor, (65, 2))


# ---------------------------------------------------------------------------
# TT-ALS: pallas vs reference, recovery, workspace contracts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "fixture,tt_ranks",
    [("tiny_tensor", (4, 4)), ("tensor4d", (3, 3, 3)), ("tensor5d", (2, 2, 2, 2))],
)
def test_tt_als_pallas_matches_reference(request, fixture, tt_ranks):
    """Acceptance: the planned Pallas TT-ALS fit history matches the pure-jnp
    reference to 1e-5 on 3/4/5-mode tensors (single device; the 2-device
    case is the sharded subprocess below)."""
    st = request.getfixturevalue(fixture)
    ref = tt_als(st, tt_ranks, iters=3, method="reference", init="random", seed=0)
    pal = tt_als(
        st, tt_ranks, iters=3, method="pallas", init="random", seed=0, cfg=SMALL_CFG
    )
    np.testing.assert_allclose(pal.fit_history, ref.fit_history, rtol=1e-5, atol=1e-5)
    assert pal.tt_ranks == tuple(tt_ranks)


def test_tt_als_eager_matches_jit_sweep(tiny_tensor):
    """jit_sweep=False (eager per-mode dispatch) is the parity baseline for
    the fused sweep, for both methods."""
    for method in ("pallas", "reference"):
        fused = tt_als(
            tiny_tensor, (3, 3), iters=2, method=method, init="random",
            seed=1, cfg=SMALL_CFG if method == "pallas" else None,
        )
        eager = tt_als(
            tiny_tensor, (3, 3), iters=2, method=method, init="random",
            seed=1, jit_sweep=False,
            cfg=SMALL_CFG if method == "pallas" else None,
        )
        np.testing.assert_allclose(
            eager.fit_history, fused.fit_history, rtol=1e-5, atol=1e-5
        )


def test_tt_als_recovers_low_tt_rank():
    """Exact recovery: an exactly-low-TT-rank tensor (full COO support) is
    fitted to ~1.0 at the generating bond ranks — SVD init lands on the
    solution and ALS keeps it."""
    st = low_tt_rank_tensor(shape=(10, 9, 8), tt_ranks=(2, 3), seed=4)
    state = tt_als(st, (2, 3), iters=3, method="pallas", init="svd", cfg=SMALL_CFG)
    assert state.fit_history[-1] > 0.999


def test_tt_als_monotone_and_tol_exit(tiny_tensor):
    """The fit is (near-)monotone over iterations and tol stops the loop
    early."""
    state = tt_als(
        tiny_tensor, (4, 4), iters=5, method="pallas", init="random", cfg=SMALL_CFG
    )
    f = state.fit_history
    assert all(b >= a - 1e-5 for a, b in zip(f, f[1:]))
    stopped = tt_als(
        tiny_tensor, (4, 4), iters=50, method="pallas", init="random",
        cfg=SMALL_CFG, tol=1e-2,
    )
    assert len(stopped.fit_history) < 50


def test_tt_als_workspace_reuse_and_validation(tiny_tensor):
    """A prebuilt PlannedTT is reused across calls; mismatched geometry or
    class is rejected by the shared check_workspace contract."""
    planned = make_planned_tt(tiny_tensor, (3, 3), cfg=SMALL_CFG)
    assert isinstance(planned, PlannedTT)
    assert planned.plan_bytes() > 0
    a = tt_als(tiny_tensor, (3, 3), iters=2, init="random", planned=planned)
    b = tt_als(tiny_tensor, (3, 3), iters=2, init="random", planned=planned)
    np.testing.assert_allclose(a.fit_history, b.fit_history, rtol=0, atol=0)

    with pytest.raises(ValueError, match="was built for"):
        tt_als(tiny_tensor, (4, 4), iters=1, planned=planned)
    with pytest.raises(ValueError, match="needs a ShardedPlannedTT"):
        tt_als(
            tiny_tensor, (3, 3), iters=1, method="pallas_sharded",
            planned=planned, devices=1,
        )
    with pytest.raises(ValueError, match="silently ignored"):
        tt_als(tiny_tensor, (3, 3), iters=1, method="reference", planned=planned)
    with pytest.raises(ValueError, match="silently ignored"):
        tt_als(tiny_tensor, (3, 3), iters=1, method="pallas", devices=2)
    with pytest.raises(ValueError, match="eager parity baseline"):
        tt_als(
            tiny_tensor, (3, 3), iters=1, method="pallas_sharded",
            devices=1, jit_sweep=False,
        )
    with pytest.raises(ValueError, match="expected 'auto', 'svd' or 'random'"):
        tt_als(tiny_tensor, (3, 3), iters=1, init="qr")
    with pytest.raises(ValueError, match="unknown method"):
        tt_als(tiny_tensor, (3, 3), iters=1, method="hooi")


# ---------------------------------------------------------------------------
# sharded parity (subprocess: the host device count locks at first jax init)
# ---------------------------------------------------------------------------


def _run(code: str, devices: int, timeout: int = 540) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=timeout, cwd=ROOT,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


_TT_PARITY_CODE = """
import jax, numpy as np
from repro.api import decompose
from repro.core.memctrl import CacheEngineConfig, DMAEngineConfig, MemoryControllerConfig
from repro.core.coo import synthetic_tensor

DEV = 2
assert jax.device_count() == DEV, jax.devices()
cfg = MemoryControllerConfig(cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
                             dma=DMAEngineConfig(blk=32))

tensors = {
    3: (synthetic_tensor((64, 48, 80), 2000, seed=0, skew=0.8), (4, 4)),
    4: (synthetic_tensor((40, 32, 48, 24), 1800, seed=2, skew=0.5), (3, 3, 3)),
    5: (synthetic_tensor((20, 25, 30, 15, 18), 1500, seed=3, skew=0.3), (2, 2, 2, 2)),
}
for nmodes, (st, tr) in tensors.items():
    ref = decompose(st, tr, format="tt", iters=2, method="reference", init="random")
    pal = decompose(st, tr, format="tt", iters=2, method="pallas", init="random", cfg=cfg)
    sh = decompose(st, tr, format="tt", iters=2, method="pallas_sharded",
                   devices=DEV, init="random", cfg=cfg)
    np.testing.assert_allclose(pal.fit_history, ref.fit_history, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sh.fit_history, ref.fit_history, rtol=1e-5, atol=1e-5)
    print(f"TT_MATCH modes={nmodes}")
print("OK")
"""


@pytest.mark.slow
def test_tt_sharded_parity_2_devices():
    """Acceptance: decompose(format='tt') — pallas AND pallas_sharded — match
    the TT reference fit to 1e-5 on 3/4/5-mode tensors under 2 forced host
    devices."""
    out = _run(_TT_PARITY_CODE, devices=2)
    assert out.count("TT_MATCH") == 3
    assert "OK" in out
