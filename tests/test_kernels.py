"""Pallas MTTKRP kernel: validation against the pure-jnp
oracles across shapes, dtypes, and memory-controller configurations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.coo import SparseTensor, frostt_like, random_factors, synthetic_tensor
from repro.core.memctrl import CacheEngineConfig, DMAEngineConfig, MemoryControllerConfig
from repro.core.remap import plan_blocks
from repro.kernels import blocked
from repro.kernels.mttkrp_pallas import mttkrp_pallas_call, pad_factor, rank_padded
from repro.kernels.ops import (
    make_planned_mttkrp,
    make_planned_ttcore,
    make_planned_ttmc,
    mttkrp_auto,
    plan_cache_clear,
    plan_cache_stats,
)
from repro.kernels.ref import mttkrp_plan_ref, mttkrp_ref, ttcore_plan_ref, ttmc_plan_ref


def _totals(stats: dict) -> tuple[int, int]:
    """(hits, misses) totals of the kind-keyed plan-cache stats."""
    return stats["hits"], stats["misses"]


def _check(st_t, mode, rank, cfg=None, rtol=2e-4):
    facs = random_factors(jax.random.PRNGKey(0), st_t.shape, rank)
    out = mttkrp_auto(st_t, facs, mode, method="pallas", cfg=cfg)
    ref = mttkrp_ref(
        jnp.asarray(st_t.indices), jnp.asarray(st_t.values), facs, mode, st_t.shape[mode]
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=rtol, atol=rtol)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_kernel_all_modes(tiny_tensor, mode):
    _check(tiny_tensor, mode, 16)


@pytest.mark.parametrize("rank", [1, 8, 16, 32, 64, 128, 130])
def test_kernel_rank_sweep(tiny_tensor, rank):
    """Ranks across/past the 128-lane boundary (R_pad logic)."""
    _check(tiny_tensor, 0, rank)


@pytest.mark.parametrize(
    "tiles",
    [(8, 8, 8, 8), (16, 8, 32, 16), (64, 64, 64, 128), (128, 128, 128, 256)],
)
def test_kernel_controller_config_sweep(tiny_tensor, tiles):
    """The paper's programmable parameters (Sec. 5.2): every legal cache/DMA
    configuration computes the same MTTKRP."""
    ti, tj, tk, blk = tiles
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=ti, tile_j=tj, tile_k=tk),
        dma=DMAEngineConfig(blk=blk),
    )
    _check(tiny_tensor, 0, 16, cfg=cfg)


def test_kernel_bf16_inputs(tiny_tensor):
    facs = [f.astype(jnp.bfloat16) for f in random_factors(jax.random.PRNGKey(0), tiny_tensor.shape, 16)]
    op = make_planned_mttkrp(tiny_tensor, 0, 16)
    out = op.output(facs, tiny_tensor.shape[0])
    ref = mttkrp_ref(
        jnp.asarray(tiny_tensor.indices),
        jnp.asarray(tiny_tensor.values),
        [f.astype(jnp.float32) for f in facs],
        0,
        tiny_tensor.shape[0],
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0.05, atol=0.05)


def test_kernel_vs_plan_ref(tiny_tensor):
    """Kernel output matches the layout-level oracle (block plan semantics),
    including padded rows."""
    plan = plan_blocks(tiny_tensor, 1, tile_i=32, tile_j=32, tile_k=32, blk=64)
    rank = 16
    rp = rank_padded(rank)
    facs = random_factors(jax.random.PRNGKey(4), tiny_tensor.shape, rank)
    pads = tuple(
        pad_factor(facs[m], rows, rp) for m, rows in zip(plan.in_modes, plan.in_rows)
    )
    ref = mttkrp_plan_ref(plan, pads, rp)
    nb = plan.nblocks
    out = mttkrp_pallas_call(
        jnp.asarray(plan.block_it),
        tuple(jnp.asarray(t) for t in plan.block_in),
        jnp.asarray(plan.vals).reshape(nb, 1, plan.blk),
        jnp.asarray(plan.iloc).reshape(nb, 1, plan.blk),
        tuple(jnp.asarray(l).reshape(nb, 1, plan.blk) for l in plan.in_locs),
        pads,
        tile_i=plan.tile_i, in_tiles=plan.in_tiles, out_rows=plan.out_rows,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def _wide_f32(n: int, seed: int) -> np.ndarray:
    """Random f32 of both signs over exponents 2^-100 .. 2^127, with +-0,
    +-1, the ends of that range and the largest finite f32."""
    rng = np.random.default_rng(seed)
    mant = rng.uniform(1.0, 2.0, n)
    x = (rng.choice([-1.0, 1.0], n) * mant * 2.0 ** rng.integers(-100, 127, n)).astype(np.float32)
    big = np.finfo(np.float32).max
    edges = [0.0, -0.0, 1.0, -1.0, 2.0**-100, -(2.0**-100), big, -big,
             np.nextafter(np.float32(1), np.float32(2))]
    x[: len(edges)] = np.asarray(edges, np.float32)
    return x


def test_pieces_sum_back_to_x_exactly():
    """hi + mid + lo, summed in f32 in that order, is x bit for bit; a zero
    comes back as a zero (a float32 sum of zeros is +0 unless all are -0,
    and a matmul's sum starts from +0 anyway)."""
    x = _wide_f32(200_000, 0)
    hi, mid, lo = jax.jit(blocked.pieces)(jnp.asarray(x))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    back = (f32(hi) + f32(mid)) + f32(lo)
    nz = x != 0
    np.testing.assert_array_equal(back[nz].view(np.uint32), x[nz].view(np.uint32))
    assert np.all(back[~nz] == 0)
    (only,) = blocked.pieces(hi)  # a bf16 array is its own single piece
    assert only is hi


def test_gather_and_rows_are_exact_in_interpret_mode():
    """The one-hot gather returns `tile[loc]` bit for bit (three bf16 passes
    lose nothing of an f32 operand), and `_rows` turns a lane vector into
    columns bit for bit."""
    from jax.experimental import pallas as pl

    tile_n, blk, w = 64, 128, 128
    tile = _wide_f32(tile_n * w, 1).reshape(tile_n, w)
    tile[tile == 0] = 1.5  # a gathered -0 comes back +0: a matmul sums from +0
    loc = np.random.default_rng(2).integers(0, tile_n, (1, blk)).astype(np.int32)
    v = _wide_f32(blk, 3)[None, :]
    v[v == 0] = -2.5

    def kernel(loc_ref, tile_ref, v_ref, rows_ref, col_ref):
        rows_ref[...] = blocked._gather(loc_ref[...], tile_ref[...])
        col_ref[...] = blocked._rows(v_ref[...], w)

    rows, col = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((blk, w), jnp.float32),
                   jax.ShapeDtypeStruct((blk, w), jnp.float32)),
        interpret=True,
    )(jnp.asarray(loc), jnp.asarray(tile), jnp.asarray(v))
    np.testing.assert_array_equal(np.asarray(rows).view(np.uint32), tile[loc[0]].view(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(col).view(np.uint32), np.broadcast_to(v.T, (blk, w)).view(np.uint32))


def _plan_case(kind, st_t, bf16_round=False):
    """(kernel output, plan-reference output) of one kernel on `st_t`, true
    columns only; with `bf16_round` the kernel's factors are first rounded
    to bf16 (the reference keeps them in f32)."""
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=32, tile_j=32, tile_k=32), dma=DMAEngineConfig(blk=64))
    mode = 1
    if kind == "mttkrp":
        op = make_planned_mttkrp(st_t, mode, 16, cfg=cfg)
        widths, cols = (16, 16), 16
    elif kind == "ttmc":
        op = make_planned_ttmc(st_t, mode, (8, 8, 8), cfg=cfg)
        widths, cols = op.in_ranks, op.out_cols
    else:
        op = make_planned_ttcore(st_t, mode, (4, 3), cfg=cfg)
        widths, cols = tuple(a * b for a, b in op.in_rank_pairs), op.out_cols
    p = op.plan
    keys = jax.random.split(jax.random.PRNGKey(7), p.n_in)
    pads = tuple(
        pad_factor(jax.random.normal(k, (st_t.shape[m], wd)) / np.sqrt(wd), rows, rank_padded(wd))
        for k, m, rows, wd in zip(keys, p.in_modes, p.in_rows, widths)
    )
    if kind == "mttkrp":
        ref = mttkrp_plan_ref(p, pads, rank_padded(16))
        run = lambda f: mttkrp_pallas_call(*op.layout, f, tile_i=p.tile_i, in_tiles=p.in_tiles,
                                           out_rows=p.out_rows)
    elif kind == "ttmc":
        ref = ttmc_plan_ref(p, pads, op.in_ranks)
        run = op.call_padded
    else:
        ref = ttcore_plan_ref(p, pads, op.in_rank_pairs, op.n_left)
        run = op.call_padded
    if bf16_round:
        pads = tuple(f.astype(jnp.bfloat16).astype(jnp.float32) for f in pads)
    return np.asarray(run(pads))[:, :cols], np.asarray(ref)[:, :cols]


@pytest.mark.parametrize("kind", ["mttkrp", "ttmc", "ttcore"])
def test_kernel_matches_plan_ref_to_float32(tiny_tensor, kind):
    """Each kernel reproduces its layout-level oracle to float32 rounding
    (1e-5), which factors rounded to bf16 miss by far: the three-pass
    matmuls keep every bit of the f32 operands."""
    out, ref = _plan_case(kind, tiny_tensor)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    rounded, _ = _plan_case(kind, tiny_tensor, bf16_round=True)
    assert np.max(np.abs(rounded - ref) / (1e-5 + 1e-5 * np.abs(ref))) > 100


@pytest.mark.parametrize("preset", ["4d_small", "5d_small"])
def test_kernel_higher_order_presets(preset):
    """Paper Table 2 has 3–5-mode tensors: the template-unrolled N-mode
    kernel must match the reference on the 4d/5d FROSTT-like presets for
    every output mode."""
    st_t = frostt_like(preset)
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=128, tile_j=128, tile_k=128),
        dma=DMAEngineConfig(blk=256),
    )
    for mode in range(st_t.nmodes):
        _check(st_t, mode, 8, cfg=cfg, rtol=5e-4)


@pytest.mark.parametrize("fixture", ["tensor4d", "tensor5d"])
@pytest.mark.parametrize("mode", [0, 1, 3])
def test_kernel_higher_order_vs_plan_ref(request, fixture, mode):
    """N-mode kernel vs the layout-level oracle, including padded rows."""
    st_t = request.getfixturevalue(fixture)
    plan = plan_blocks(st_t, mode, tile_i=16, tile_j=16, tile_k=16, blk=32)
    assert plan.n_in == st_t.nmodes - 1
    rank = 8
    rp = rank_padded(rank)
    facs = random_factors(jax.random.PRNGKey(6), st_t.shape, rank)
    pads = tuple(
        pad_factor(facs[m], rows, rp) for m, rows in zip(plan.in_modes, plan.in_rows)
    )
    ref = mttkrp_plan_ref(plan, pads, rp)
    op = make_planned_mttkrp(
        st_t, mode, rank,
        cfg=MemoryControllerConfig(
            cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
            dma=DMAEngineConfig(blk=32),
        ),
    )
    out = op.output(facs, st_t.shape[mode])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref)[: st_t.shape[mode], :rank], rtol=1e-4, atol=1e-4
    )


def test_mttkrp_auto_unsorted_stream_approach1(tiny_tensor):
    """Regression (PR 2): `mttkrp_auto` used to promise sorted_by_mode=True
    to XLA for the raw (unsorted) COO stream — `indices_are_sorted` is a
    correctness contract, not a hint.  The dispatcher must derive the flag
    from what the stream actually satisfies and still compute the exact
    MTTKRP on an unsorted stream."""
    rng = np.random.default_rng(11)
    perm = rng.permutation(tiny_tensor.nnz)
    shuffled = SparseTensor(
        tiny_tensor.indices[perm], tiny_tensor.values[perm], tiny_tensor.shape
    )
    assert not shuffled.is_sorted_by(0)
    facs = random_factors(jax.random.PRNGKey(8), shuffled.shape, 16)
    out = mttkrp_auto(shuffled, facs, 0, method="approach1")
    ref = mttkrp_ref(
        jnp.asarray(shuffled.indices), jnp.asarray(shuffled.values),
        facs, 0, shuffled.shape[0],
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    # a sorted stream still takes the fast path
    srt = shuffled.sorted_by(0)
    out_s = mttkrp_auto(srt, facs, 0, method="approach1")
    ref_s = mttkrp_ref(
        jnp.asarray(srt.indices), jnp.asarray(srt.values), facs, 0, srt.shape[0]
    )
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref_s), rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(
    nnz=st.integers(1, 300),
    dims=st.tuples(st.integers(4, 60), st.integers(4, 60), st.integers(4, 60)),
    mode=st.integers(0, 2),
    seed=st.integers(0, 99),
    blk=st.sampled_from([8, 32]),
)
def test_kernel_property_random_shapes(nnz, dims, mode, seed, blk):
    """Property: kernel == oracle for arbitrary tensors and DMA buffer sizes
    (tile/padding edge cases: tiny modes, empty tiles, one-element blocks)."""
    st_t = synthetic_tensor(dims, nnz, seed=seed, skew=0.6)
    cfg = MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
        dma=DMAEngineConfig(blk=blk),
    )
    _check(st_t, mode, 8, cfg=cfg, rtol=5e-4)


def test_plan_cache_hits_and_counters(tiny_tensor):
    """mttkrp_auto(method='pallas') must not rebuild the BlockPlan on every
    call: same (tensor, mode, rank, cfg) -> cache hit; a different mode or
    config -> miss.  Counters feed bench_e2e."""
    import repro.kernels.ops as ops_mod

    plan_cache_clear()
    assert _totals(plan_cache_stats()) == (0, 0)
    calls = []
    orig = ops_mod.plan_blocks

    def counting(*a, **k):
        calls.append(a)
        return orig(*a, **k)

    facs = random_factors(jax.random.PRNGKey(0), tiny_tensor.shape, 8)
    try:
        ops_mod.plan_blocks = counting
        out1 = mttkrp_auto(tiny_tensor, facs, 0, method="pallas")
        out2 = mttkrp_auto(tiny_tensor, facs, 0, method="pallas")
        assert len(calls) == 1  # second call served from the plan cache
        assert _totals(plan_cache_stats()) == (1, 1)
        # mttkrp_auto's traffic is tracked under its own kernel kind
        assert plan_cache_stats()["by_kind"]["mttkrp"] == {"hits": 1, "misses": 1}
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        mttkrp_auto(tiny_tensor, facs, 1, method="pallas")  # new mode -> miss
        assert _totals(plan_cache_stats()) == (1, 2)
        cfg = MemoryControllerConfig(
            cache=CacheEngineConfig(tile_i=32, tile_j=32, tile_k=32),
            dma=DMAEngineConfig(blk=32),
        )
        mttkrp_auto(tiny_tensor, facs, 0, method="pallas", cfg=cfg)  # new cfg -> miss
        assert _totals(plan_cache_stats()) == (1, 3)
        assert len(calls) == 3
    finally:
        ops_mod.plan_blocks = orig
        plan_cache_clear()


def test_plan_cache_keys_on_content(tiny_tensor):
    """The cache key is a content fingerprint: a distinct SparseTensor object
    with identical contents hits; changing one value misses."""
    plan_cache_clear()
    facs = random_factors(jax.random.PRNGKey(1), tiny_tensor.shape, 8)
    mttkrp_auto(tiny_tensor, facs, 0, method="pallas")
    clone = SparseTensor(
        tiny_tensor.indices.copy(), tiny_tensor.values.copy(), tiny_tensor.shape
    )
    mttkrp_auto(clone, facs, 0, method="pallas")
    assert _totals(plan_cache_stats()) == (1, 1)
    bumped = SparseTensor(
        tiny_tensor.indices.copy(),
        np.concatenate([[np.float32(2.0) * tiny_tensor.values[0]], tiny_tensor.values[1:]]),
        tiny_tensor.shape,
    )
    mttkrp_auto(bumped, facs, 0, method="pallas")
    assert _totals(plan_cache_stats()) == (1, 2)
    plan_cache_clear()


def test_kernel_single_flush_traffic(tiny_tensor):
    """Approach-1 traffic property on the real layout: number of A-tile
    fills equals the number of occupied output tiles (each flushed once)."""
    plan = plan_blocks(tiny_tensor, 0, tile_i=16, tile_j=16, tile_k=16, blk=32)
    fills = plan.tile_fills()
    occupied = np.unique(tiny_tensor.indices[:, 0] // 16).size
    assert fills["A"] == occupied
    assert plan.a_tile_single_flush()
