"""Distributed planned decomposition (repro.dist.planned).

Three layers of coverage:
  * host-side partitioner properties — `partition_stream` must cover the
    stream exactly (no dropped/duplicated non-zeros at tile boundaries),
    keep boundaries tile-aligned, and reassemble the original order;
  * in-process single-shard checks — the sharded machinery runs on a 1-device
    `shard` mesh in this very process (shard_map over one device), so the
    whole path is exercised without subprocesses; plus API error contracts
    and the sharded PMS;
  * subprocess parity — `pallas_sharded` vs single-device `pallas` fit match
    to 1e-5 on 3/4/5-mode tensors under forced 2- and 4-device host
    platforms (the host device count locks at first jax init, hence the
    `_run` pattern shared with test_mttkrp_sharded / test_dist).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

from repro.core.coo import synthetic_tensor
from repro.core.memctrl import (
    CacheEngineConfig,
    DMAEngineConfig,
    MemoryControllerConfig,
)
from repro.dist.sharding import partition_stream

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL_CFG = MemoryControllerConfig(
    cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
    dma=DMAEngineConfig(blk=32),
)


# ---------------------------------------------------------------------------
# partitioner properties (host-side numpy, no devices involved)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    dims=hst.tuples(hst.integers(4, 70), hst.integers(4, 70), hst.integers(4, 70)),
    nnz=hst.integers(1, 1_500),
    nshards=hst.integers(1, 6),
    tile=hst.sampled_from([1, 7, 16, 64]),
    mode=hst.integers(0, 2),
    seed=hst.integers(0, 99),
)
def test_partition_reassembles_exact_stream(dims, nnz, nshards, tile, mode, seed):
    """No dropped or duplicated non-zeros at tile boundaries: the shards are
    a disjoint cover and scatter back to the exact original stream, order
    included."""
    st = synthetic_tensor(dims, nnz, seed=seed, skew=0.7)
    part = partition_stream(st, mode, nshards, tile=tile)
    assert part.nshards == nshards
    assert sum(part.shard_nnz) == st.nnz
    re = part.reassemble()
    np.testing.assert_array_equal(re.indices, st.indices)
    np.testing.assert_array_equal(re.values, st.values)
    # tile-aligned disjoint ownership + original relative order per shard
    for (a, b), sh, pos in zip(part.row_ranges(), part.shards, part.positions):
        assert a % tile == 0 or a == st.shape[mode]
        if sh.nnz:
            c = sh.indices[:, mode]
            assert a <= c.min() and c.max() < b
            assert np.all(np.diff(pos) > 0)  # stable within shard


@settings(max_examples=10, deadline=None)
@given(
    nnz=hst.integers(64, 2_000),
    nshards=hst.sampled_from([2, 4]),
    seed=hst.integers(0, 20),
)
def test_partition_balances_when_tiles_allow(nnz, nshards, seed):
    """With many more tiles than shards and mild skew, the greedy prefix
    split must stay within 2x of a perfect balance (it can only miss the
    quantile by one tile's worth of nnz)."""
    st = synthetic_tensor((256, 64, 64), nnz, seed=seed, skew=0.3)
    part = partition_stream(st, 0, nshards, tile=4)
    assert part.imbalance() < 2.0


def test_partition_validates_arguments():
    st = synthetic_tensor((8, 8, 8), 64, seed=0)
    with pytest.raises(ValueError, match="nshards"):
        partition_stream(st, 0, 0)
    with pytest.raises(ValueError, match="mode"):
        partition_stream(st, 3, 2)
    with pytest.raises(ValueError, match="tile"):
        partition_stream(st, 0, 2, tile=0)


def test_partition_more_shards_than_tiles():
    """Degenerate regime: empty shards appear, coverage still exact."""
    st = synthetic_tensor((8, 8, 8), 100, seed=1)
    part = partition_stream(st, 0, 5, tile=8)  # one tile, five shards
    assert sum(part.shard_nnz) == st.nnz
    assert sum(1 for n in part.shard_nnz if n == 0) >= 4
    re = part.reassemble()
    np.testing.assert_array_equal(re.indices, st.indices)


# ---------------------------------------------------------------------------
# sharded PMS
# ---------------------------------------------------------------------------


def test_predict_sharded_is_makespan(small_tensor):
    from repro.core.pms import predict_sharded

    est = predict_sharded(small_tensor, 0, 16, 4, MemoryControllerConfig())
    assert est.nshards == 4
    assert est.t_total == max(e.t_total for e in est.per_shard)
    assert est.per_shard[est.critical_shard].t_total == est.t_total
    assert est.imbalance >= 1.0
    assert est.vmem_bytes == est.per_shard[0].vmem_bytes


def test_search_sharded_ranks_by_worst_shard(small_tensor):
    from repro.core.pms import search_sharded

    spec_kw = dict(top_k=4)
    best = search_sharded(small_tensor, 0, 16, 2, **spec_kw)
    assert best, "no VMEM-feasible sharded configuration"
    makespans = [e.t_total for e in best]
    assert makespans == sorted(makespans)
    # ttmc kernel needs the full core-rank tuple
    with pytest.raises(ValueError, match="core_ranks"):
        search_sharded(small_tensor, 0, 16, 2, kernel="ttmc")
    bt = search_sharded(
        small_tensor, 0, 16, 2, kernel="ttmc", core_ranks=(8, 8, 8), top_k=2
    )
    assert bt and bt[0].t_total <= bt[-1].t_total


def test_predict_sharded_handles_empty_shards():
    from repro.core.pms import predict_sharded

    st = synthetic_tensor((8, 8, 8), 50, seed=0)
    est = predict_sharded(st, 0, 8, 4, MemoryControllerConfig())  # 1 tile, 4 shards
    assert est.t_total > 0.0
    assert sum(1 for e in est.per_shard if e.t_total == 0.0) >= 3


# ---------------------------------------------------------------------------
# in-process single-shard path + API contracts
# ---------------------------------------------------------------------------


def test_sharded_path_on_one_device_matches_pallas(tiny_tensor):
    """devices=1 runs the full sharded machinery (partition, stack,
    shard_map, psum, masked tiles) on the lone CPU device — fit must match
    the single-device planned path to 1e-5."""
    from repro.core.cp_als import cp_als

    ref = cp_als(tiny_tensor, 8, iters=2, method="pallas", cfg=SMALL_CFG)
    sh = cp_als(tiny_tensor, 8, iters=2, method="pallas_sharded", devices=1,
                cfg=SMALL_CFG)
    np.testing.assert_allclose(sh.fit_history, ref.fit_history, rtol=1e-5, atol=1e-5)


def test_sharded_tucker_on_one_device_matches_pallas(tiny_tensor):
    from repro.tucker import tucker_hooi

    ref = tucker_hooi(tiny_tensor, (4, 4, 4), iters=2, method="pallas", cfg=SMALL_CFG)
    sh = tucker_hooi(tiny_tensor, (4, 4, 4), iters=2, method="pallas_sharded",
                     devices=1, cfg=SMALL_CFG)
    np.testing.assert_allclose(sh.fit_history, ref.fit_history, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sh.core), np.asarray(ref.core), rtol=2e-4, atol=2e-4
    )


def test_empty_intra_range_tiles_are_zero_not_nan():
    """Regression: an output tile with NO non-zeros inside a plan's range is
    never visited by the kernel.  Both the single-device planned path and
    the sharded path must return exact zeros there, not whatever the output
    buffer held."""
    import jax

    from repro.core.coo import SparseTensor, random_factors
    from repro.core.cp_als import cp_als
    from repro.kernels import ops

    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=8, tile_j=16, tile_k=16),
        dma=DMAEngineConfig(blk=32),
    )
    st0 = synthetic_tensor((64, 48, 80), 3000, seed=5, skew=0.5)
    keep = (st0.indices[:, 0] < 16) | (st0.indices[:, 0] >= 24)
    st = SparseTensor(st0.indices[keep], st0.values[keep], st0.shape)  # tile 2 empty
    facs = random_factors(jax.random.PRNGKey(0), st.shape, 8)

    ref = np.asarray(ops.mttkrp_auto(st, facs, 0, method="approach1"))
    got = np.asarray(ops.mttkrp_auto(st, facs, 0, cfg=cfg))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    assert np.all(got[16:24] == 0.0)

    # whole decompositions stay finite and match across paths
    s_ref = cp_als(st, 8, iters=2, method="pallas", cfg=cfg)
    assert np.isfinite(s_ref.fit_history).all()
    s_sh = cp_als(st, 8, iters=2, method="pallas_sharded", devices=1, cfg=cfg)
    np.testing.assert_allclose(s_sh.fit_history, s_ref.fit_history, rtol=1e-5, atol=1e-5)


def test_sharded_mttkrp_route_and_cache_keys(tiny_tensor):
    """mttkrp_sharded(method='pallas') matches mttkrp_auto; per-shard plans
    land in the shared cache under shard-aware keys (kind counters move)."""
    import jax

    from repro.core.coo import random_factors
    from repro.core.mttkrp import mttkrp_sharded
    from repro.dist.planned import shard_plan
    from repro.kernels import ops

    facs = random_factors(jax.random.PRNGKey(0), tiny_tensor.shape, 8)
    ref = ops.mttkrp_auto(tiny_tensor, facs, 0, cfg=SMALL_CFG)
    ops.plan_cache_clear()
    plan = shard_plan(1)
    fn = mttkrp_sharded(plan, 0, tiny_tensor.shape[0], method="pallas",
                        st=tiny_tensor, rank=8, cfg=SMALL_CFG)
    got = fn(None, None, facs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
    stats = ops.plan_cache_stats()
    assert stats["by_kind"]["mttkrp"]["misses"] >= 1
    # identical rebuild hits the shard-keyed entries instead of re-remapping
    fn2 = mttkrp_sharded(plan, 0, tiny_tensor.shape[0], method="pallas",
                         st=tiny_tensor, rank=8, cfg=SMALL_CFG)
    stats2 = ops.plan_cache_stats()
    assert stats2["by_kind"]["mttkrp"]["hits"] > stats["by_kind"]["mttkrp"]["hits"]
    # shard entries cache raw BlockPlans, which don't depend on rank — a
    # rebuild at another rank must hit, not repay the Tensor Remapper
    mttkrp_sharded(plan, 0, tiny_tensor.shape[0], method="pallas",
                   st=tiny_tensor, rank=4, cfg=SMALL_CFG)
    stats3 = ops.plan_cache_stats()
    assert stats3["by_kind"]["mttkrp"]["hits"] > stats2["by_kind"]["mttkrp"]["hits"]
    assert stats3["by_kind"]["mttkrp"]["misses"] == stats2["by_kind"]["mttkrp"]["misses"]
    # shard layouts are kernel-agnostic BlockPlans: a Tucker workspace on
    # the same (tensor, cfg) reuses the CP build's mode-0 shard layout
    # (stats attributed to the ttmc kind, key shared)
    from repro.kernels.ops import make_sharded_planned_tucker

    before = ops.plan_cache_stats()["by_kind"]["ttmc"]
    make_sharded_planned_tucker(tiny_tensor, (4, 4, 4), dist=plan, cfg=SMALL_CFG)
    after = ops.plan_cache_stats()["by_kind"]["ttmc"]
    assert after["hits"] >= before["hits"] + 1


def test_sharded_api_contracts(tiny_tensor):
    from repro.core.cp_als import cp_als
    from repro.core.mttkrp import mttkrp_sharded
    from repro.dist.planned import shard_plan
    from repro.tucker import tucker_hooi

    with pytest.raises(ValueError, match="sweep-only|jitted shard_map"):
        cp_als(tiny_tensor, 4, iters=1, method="pallas_sharded", devices=1,
               jit_sweep=False)
    with pytest.raises(ValueError, match="sweep-only|jitted shard_map"):
        tucker_hooi(tiny_tensor, (2, 2, 2), iters=1, method="pallas_sharded",
                    devices=1, jit_sweep=False)
    with pytest.raises(ValueError, match="st="):
        mttkrp_sharded(shard_plan(1), 0, tiny_tensor.shape[0], method="pallas")
    with pytest.raises(ValueError, match="devices"):
        shard_plan(0)
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        shard_plan(4096)
    # a single-device workspace cannot be passed to the sharded method
    from repro.kernels.ops import make_planned_cp_als

    ws = make_planned_cp_als(tiny_tensor, 4, cfg=SMALL_CFG)
    with pytest.raises(ValueError, match="ShardedPlannedCPALS"):
        cp_als(tiny_tensor, 4, iters=1, method="pallas_sharded", planned=ws)


def test_bench_fast_refuses_baseline_path():
    """The non-clobber contract is enforced in code, not by path convention:
    a --fast run pointed at the committed baseline must die loudly."""
    sys.path.insert(0, ROOT)
    try:
        from benchmarks.bench_e2e import BASELINE_PATH, _resolve_out

        with pytest.raises(SystemExit, match="refusing to overwrite"):
            _resolve_out(None, fast=True)
        with pytest.raises(SystemExit, match="refusing to overwrite"):
            _resolve_out(str(BASELINE_PATH), fast=True)
        assert _resolve_out("/tmp/scratch.json", fast=True).name == "scratch.json"
        assert _resolve_out(None, fast=False) == BASELINE_PATH
    finally:
        sys.path.remove(ROOT)


# ---------------------------------------------------------------------------
# multi-device parity (subprocess: the host device count locks at jax init)
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


_PARITY_CODE = """
import jax, numpy as np
from repro.core.coo import synthetic_tensor
from repro.core.cp_als import cp_als
from repro.core.memctrl import CacheEngineConfig, DMAEngineConfig, MemoryControllerConfig
from repro.tucker import tucker_hooi

DEV = {devices}
assert jax.device_count() == DEV, jax.devices()
cfg = MemoryControllerConfig(cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
                             dma=DMAEngineConfig(blk=32))

tensors = {{
    3: synthetic_tensor((64, 48, 80), 2000, seed=0, skew=0.8),
    4: synthetic_tensor((40, 32, 48, 24), 1800, seed=2, skew=0.5),
    5: synthetic_tensor((20, 25, 30, 15, 18), 1500, seed=3, skew=0.3),
}}
for nmodes, st in tensors.items():
    ref = cp_als(st, 8, iters=2, method="pallas", cfg=cfg)
    sh = cp_als(st, 8, iters=2, method="pallas_sharded", devices=DEV, cfg=cfg)
    np.testing.assert_allclose(sh.fit_history, ref.fit_history, rtol=1e-5, atol=1e-5)
    print(f"CP_MATCH modes={{nmodes}}")

st = tensors[{tucker_modes}]
ranks = (3,) * {tucker_modes}
t_ref = tucker_hooi(st, ranks, iters=2, method="pallas", cfg=cfg)
t_sh = tucker_hooi(st, ranks, iters=2, method="pallas_sharded", devices=DEV, cfg=cfg)
np.testing.assert_allclose(t_sh.fit_history, t_ref.fit_history, rtol=1e-5, atol=1e-5)
print("TUCKER_MATCH")
print("OK")
"""


@pytest.mark.slow
def test_sharded_parity_2_devices():
    """pallas_sharded == pallas to 1e-5 on 3/4/5-mode tensors, 2 devices,
    plus Tucker HOOI on the 3-mode tensor."""
    out = _run(_PARITY_CODE.format(devices=2, tucker_modes=3), devices=2)
    assert out.count("CP_MATCH") == 3
    assert "TUCKER_MATCH" in out and "OK" in out


@pytest.mark.slow
def test_sharded_parity_4_devices():
    """Same parity under 4 forced host devices; Tucker rides on the 4-mode
    tensor to cover the N-mode TTMc kernel under sharding."""
    out = _run(_PARITY_CODE.format(devices=4, tucker_modes=4), devices=4)
    assert out.count("CP_MATCH") == 3
    assert "TUCKER_MATCH" in out and "OK" in out
