"""Performance Model Simulator (paper Sec. 5.3): fit constraint, search
ordering, and exact-vs-analytic agreement."""
import numpy as np
import pytest

from repro.core.memctrl import (
    CacheEngineConfig,
    DMAEngineConfig,
    MemoryControllerConfig,
    TPUSpec,
)
from repro.core.pms import (
    predict_analytic,
    predict_from_plan,
    predict_ttmc,
    predict_ttmc_analytic,
    search,
)
from repro.core.remap import plan_blocks
from repro.core.hypergraph import stats


def test_vmem_model_counts_all_engines():
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=256, tile_j=512, tile_k=128),
        dma=DMAEngineConfig(blk=256, buffers=2),
    )
    rp = 128
    want = (
        # double-buffered: accumulator carried in and written out, the
        # factor tiles, and four (1, blk) stream rows padded to 8 sublanes
        2 * ((2 * 256 * rp + (512 + 128) * rp) * 4 + 8 * 256 * (4 + 12))
        + 256 * (512 + 256) * 4  # one-hot gather and segment matrices
        + 256 * 2 * rp * 4  # gathered rows and their running product
    )
    assert cfg.vmem_bytes(rp) == want


def test_search_respects_vmem_budget(small_tensor):
    spec = TPUSpec()
    res = search(small_tensor, 0, 64, spec=spec, top_k=50)
    assert res, "search returned nothing"
    for e in res:
        assert e.vmem_bytes <= spec.vmem_bytes * spec.vmem_usable_frac
    # sorted by predicted total time
    times = [e.t_total for e in res]
    assert times == sorted(times)


def test_search_excludes_oversized_configs(small_tensor):
    """A tile choice that cannot fit VMEM must never be returned."""
    res = search(
        small_tensor, 0, 2048,  # R_pad 2048 x 8192-row tiles >> 64 MiB budget
        tile_choices=(8192,), blk_choices=(1024,), top_k=10,
    )
    assert res == []


def test_exact_prediction_uses_measured_fills(small_tensor):
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=256, tile_j=256, tile_k=256),
        dma=DMAEngineConfig(blk=256),
    )
    plan = plan_blocks(small_tensor, 0, tile_i=256, tile_j=256, tile_k=256, blk=256)
    est = predict_from_plan(plan, 16, cfg)
    fills = plan.tile_fills()
    spec = TPUSpec()
    rp = 128
    assert est.t_factor == pytest.approx(
        (fills["B"] * 256 + fills["C"] * 256) * rp * 4 / spec.hbm_bw
    )
    assert est.t_out == pytest.approx(fills["A"] * 256 * rp * 4 / spec.hbm_bw)
    assert est.nblocks == plan.nblocks
    assert est.bottleneck in ("memory", "compute")


def test_analytic_within_factor_of_exact(small_tensor):
    """The occupancy model should land within ~3x of the measured layout for
    a moderately skewed tensor (it is intentionally conservative)."""
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=256, tile_j=256, tile_k=256),
        dma=DMAEngineConfig(blk=256),
    )
    plan = plan_blocks(small_tensor, 0, tile_i=256, tile_j=256, tile_k=256, blk=256)
    exact = predict_from_plan(plan, 16, cfg)
    approx = predict_analytic(stats(small_tensor), 0, 16, cfg)
    assert approx.t_total / exact.t_total < 3.0
    assert exact.t_total / approx.t_total < 3.0


def test_vmem_model_ttmc_counts_core_tile():
    """The TTMc VMEM model pays the core-tensor slice width (Pp lanes) on
    the accumulator tile and each input factor's own lane padding."""
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=256, tile_j=512, tile_k=128),
        dma=DMAEngineConfig(blk=256, buffers=2),
    )
    pp, in_rps = 256, (128, 128)
    want = (
        2 * ((2 * 256 * pp + (512 + 128) * 128) * 4 + 8 * 256 * (4 + 12))
        + 256 * (512 + 256) * 4
        + 256 * (128 + 2 * pp) * 4  # widest gathered rows, spread, product
    )
    assert cfg.vmem_bytes_ttmc(pp, in_rps) == want
    # the kron widening makes TTMc strictly hungrier than MTTKRP at equal rank
    assert cfg.vmem_bytes_ttmc(256, (128, 128)) > cfg.vmem_bytes(128)


def test_predict_ttmc_uses_measured_fills(small_tensor):
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=256, tile_j=256, tile_k=256),
        dma=DMAEngineConfig(blk=256),
    )
    plan = plan_blocks(small_tensor, 0, tile_i=256, tile_j=256, tile_k=256, blk=256)
    core_ranks = (8, 8, 8)
    est = predict_ttmc(plan, core_ranks, cfg)
    fills = plan.tile_fills()
    spec = TPUSpec()
    # input factors each pad their own rank to 128; the output pays Pp=128
    assert est.t_factor == pytest.approx(
        (fills["B"] * 256 + fills["C"] * 256) * 128 * 4 / spec.hbm_bw
    )
    assert est.t_out == pytest.approx(fills["A"] * 256 * 128 * 4 / spec.hbm_bw)
    assert est.nblocks == plan.nblocks
    # stream term identical to the MTTKRP model: the layout is shared
    assert est.t_stream == pytest.approx(predict_from_plan(plan, 8, cfg).t_stream)


def test_ttmc_analytic_within_factor_of_exact(small_tensor):
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=256, tile_j=256, tile_k=256),
        dma=DMAEngineConfig(blk=256),
    )
    plan = plan_blocks(small_tensor, 0, tile_i=256, tile_j=256, tile_k=256, blk=256)
    exact = predict_ttmc(plan, (8, 8, 8), cfg)
    approx = predict_ttmc_analytic(stats(small_tensor), 0, (8, 8, 8), cfg)
    assert approx.t_total / exact.t_total < 3.0
    assert exact.t_total / approx.t_total < 3.0


def test_search_kernel_ttmc(small_tensor):
    """The per-kernel search: TTMc candidates respect the TTMc VMEM fit, and
    a core-rank tuple whose Kronecker width blows the budget prunes configs
    that MTTKRP at the same per-mode rank would keep."""
    spec = TPUSpec()
    res = search(small_tensor, 0, 16, kernel="ttmc", core_ranks=(16, 16, 16), top_k=20)
    assert res, "ttmc search returned nothing"
    for e in res:
        assert e.vmem_bytes <= spec.vmem_bytes * spec.vmem_usable_frac
    times = [e.t_total for e in res]
    assert times == sorted(times)
    # kron width 64*64=4096 lanes on an 8192-row output tile >> budget
    wide = search(
        small_tensor, 0, 16, kernel="ttmc", core_ranks=(64, 64, 64),
        tile_choices=(8192,), blk_choices=(1024,), top_k=10,
    )
    assert wide == []


def test_search_validates_kernel_args(small_tensor):
    with pytest.raises(ValueError, match="kernel"):
        search(small_tensor, 0, 16, kernel="ttm")
    with pytest.raises(ValueError, match="core_ranks"):
        search(small_tensor, 0, 16, kernel="ttmc")
    with pytest.raises(ValueError, match="N-tuple"):
        # natural mistake: the N-1 input ranks instead of the full N-tuple
        search(small_tensor, 0, 16, kernel="ttmc", core_ranks=(8, 8))


def test_mttkrp_is_memory_bound_at_paper_scale(small_tensor):
    """The paper's premise: spMTTKRP on real tensors is memory-bound.  At
    the ALGORITHMIC level (Table 1 traffic vs N*|T|*R MACs on v5e numbers)
    the memory term dominates by orders of magnitude.  (Note: the *kernel*
    may still become MXU-compute-bound because the one-hot segment matmul
    trades FLOPs for streaming — that trade is measured in bench_kernel.)"""
    from repro.core.hypergraph import approach1_traffic

    spec = TPUSpec()
    t = approach1_traffic(small_tensor, 0, 16)
    t_mem = t.bytes() / spec.hbm_bw
    t_cmp = 2 * t.compute_ops / spec.peak_flops
    assert t_mem > 10 * t_cmp


def _hand_plan(block_it, block_in, *, blk=4, tile_i=8, in_tiles=(16, 32)):
    from repro.core.remap import BlockPlan

    nb = len(block_it)
    zeros = np.zeros(nb * blk, np.int32)
    return BlockPlan(
        vals=np.zeros(nb * blk, np.float32), iloc=zeros,
        in_locs=(zeros,) * len(in_tiles),
        block_it=np.asarray(block_it, np.int32),
        block_in=tuple(np.asarray(t, np.int32) for t in block_in),
        tile_i=tile_i, in_tiles=in_tiles, blk=blk, out_rows=2 * tile_i,
        in_rows=tuple(4 * t for t in in_tiles), mode=0, in_modes=(1, 2), nnz=nb,
    )


def test_kernel_fetch_bytes_hand_count():
    """Six grid steps in calls of four: every tile is fetched at the first
    step of each call and at each change of its id; the accumulator tile is
    read and written at each of its fills."""
    from repro.core.memctrl import RemapperConfig
    from repro.core.pms import kernel_fetch_bytes

    plan = _hand_plan([0, 0, 0, 1, 1, 1], ([0, 1, 1, 2, 2, 2], [5, 5, 5, 5, 6, 6]))
    # whole grid: A 0|1 -> 2, B 0|1|2 -> 3, C 5|6 -> 2; calls of four add a
    # fresh fetch of every tile at step 4: A 3, B 4, C 2 (C changes there).
    assert plan.tile_fills() == {"A": 2, "B": 3, "C": 2}
    assert plan.tile_fills(chunk=4) == {"A": 3, "B": 4, "C": 2}
    stream = 6 * 4 * (4 + 3 * 4)
    factor = (4 * 16 * 128 + 2 * 32 * 256) * 4
    acc = 3 * 8 * 128 * 4
    got = kernel_fetch_bytes(plan, (128, 256), 128, RemapperConfig(), chunk=4)
    assert got == stream + factor + 2 * acc == 123_264


def test_kernel_fetch_bytes_matches_pms_byte_terms(small_tensor):
    """Over one call (no chunk boundary) the fetch count is the PMS's own
    byte terms, with the accumulator read as well as written — MTTKRP at one
    lane width, TTMc at each input rank's and the Kronecker width."""
    from repro.core.pms import kernel_fetch_bytes

    spec = TPUSpec()
    cfg = MemoryControllerConfig()
    plan = plan_blocks(small_tensor, 0)
    one_call = plan.nblocks
    est = predict_from_plan(plan, 16, cfg, spec)
    got = kernel_fetch_bytes(plan, (128, 128), 128, cfg.remapper, chunk=one_call)
    assert got == pytest.approx((est.t_stream + est.t_factor + 2 * est.t_out) * spec.hbm_bw,
                                rel=1e-12)
    est = predict_ttmc(plan, (8, 130, 20), cfg, spec)  # in_modes (1, 2): ranks 130, 20
    got = kernel_fetch_bytes(plan, (256, 128), 2688, cfg.remapper, chunk=one_call)
    assert got == pytest.approx((est.t_stream + est.t_factor + 2 * est.t_out) * spec.hbm_bw,
                                rel=1e-12)
    # calls of fewer steps only add fresh fetches
    assert kernel_fetch_bytes(plan, (128, 128), 128, cfg.remapper, chunk=7) > \
        kernel_fetch_bytes(plan, (128, 128), 128, cfg.remapper, chunk=one_call)


def test_workspace_records_fetch_bytes_once(small_tensor):
    """Each single-device workspace counts its kernels' fetches at build,
    with the chunking and lane widths its kernels run, and records them as
    `kernel.fetch_bytes{mode=}`."""
    from repro.core.pms import kernel_fetch_bytes
    from repro.kernels.blocked import chunk_blocks
    from repro.kernels.ops import make_planned_cp_als
    from repro.obs import metrics
    from repro.tucker.hooi import make_planned_tucker

    metrics.reset()
    ws = make_planned_cp_als(small_tensor, 16)
    for m, op in ws.ops.items():
        assert ws.fetch_bytes[m] == kernel_fetch_bytes(
            op.plan, (128, 128), 128, op.cfg.remapper, chunk_blocks(3))
    gauges = metrics.snapshot()["gauges"]
    assert {k: v for k, v in gauges.items() if k.startswith("kernel.fetch_bytes")} == {
        f"kernel.fetch_bytes{{mode={m}}}": float(b) for m, b in ws.fetch_bytes.items()}
    tk = make_planned_tucker(small_tensor, (8, 130, 20))
    p = tk.ops[0].plan  # input ranks 130 and 20 (lanes 256, 128); 2600 columns -> 2688
    assert tk.fetch_bytes[0] == kernel_fetch_bytes(p, (256, 128), 2688, tk.ops[0].cfg.remapper,
                                                   chunk_blocks(3))
    metrics.reset()


def test_workspace_records_grid_steps_once(small_tensor):
    """Each single-device workspace records its kernels' grid steps at
    build as `kernel.grid_steps{mode=}`: each mode's plan's blocks."""
    from repro.kernels.ops import make_planned_cp_als
    from repro.obs import metrics

    metrics.reset()
    ws = make_planned_cp_als(small_tensor, 16)
    gauges = metrics.snapshot()["gauges"]
    assert {k: v for k, v in gauges.items() if k.startswith("kernel.grid_steps")} == {
        f"kernel.grid_steps{{mode={m}}}": float(op.plan.nblocks) for m, op in ws.ops.items()}
    metrics.reset()
