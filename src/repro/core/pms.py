"""Performance Model Simulator (paper Sec. 5.3), retargeted to TPU.

The paper's PMS estimates spMTTKRP execution time for a controller
configuration + dataset, and checks the configuration fits on-chip memory, so
the (hours-long) synthesis loop never runs on a bad configuration.  Our PMS
does the same for the Pallas kernel: given tensor statistics (or an actual
BlockPlan) and a MemoryControllerConfig, estimate the three roofline terms and
search the parameter space under the VMEM budget.  Re-instantiating the kernel
is a re-jit (seconds), but the model is still what makes the search tractable
for large datasets.

Model (per output mode):
  t_stream  = stream_bytes / hbm_bw          (DMA Engine term)
  t_factor  = tile_fill_bytes / hbm_bw       (Cache Engine miss term)
  t_out     = out_tile_bytes / hbm_bw        (single flush per A tile; Approach 1)
  t_mem     = t_stream + t_factor + t_out
  t_compute = kernel_flops / peak_flops      (MXU one-hot segment matmul)
  t_total   ~= max(t_mem, t_compute)         (double-buffered overlap)
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Iterable, Sequence

import numpy as np

from .coo import SparseTensor
from .hypergraph import HypergraphStats, stats as hg_stats
from .memctrl import MemoryControllerConfig, CacheEngineConfig, DMAEngineConfig, RemapperConfig, TPUSpec
from .remap import BlockPlan, plan_blocks

__all__ = [
    "PMSEstimate",
    "ShardedPMSEstimate",
    "predict_from_plan",
    "predict_analytic",
    "predict_ttmc",
    "predict_ttmc_analytic",
    "predict_tt",
    "predict_tt_analytic",
    "predict_sharded",
    "resolve_spec",
    "search",
    "search_sharded",
    "DEFAULT_TILE_CHOICES",
    "kernel_fetch_bytes",
    "tile_bytes",
]


@dataclasses.dataclass(frozen=True)
class PMSEstimate:
    cfg: MemoryControllerConfig
    t_stream: float
    t_factor: float
    t_out: float
    t_compute: float
    vmem_bytes: int
    nblocks: int
    padding_fraction: float

    @property
    def t_mem(self) -> float:
        return self.t_stream + self.t_factor + self.t_out

    @property
    def t_total(self) -> float:
        return max(self.t_mem, self.t_compute)

    @property
    def bottleneck(self) -> str:
        return "memory" if self.t_mem >= self.t_compute else "compute"


def _rank_padded(rank: int) -> int:
    return max(128, ((rank + 127) // 128) * 128)


def resolve_spec(spec) -> TPUSpec:
    """Resolve the `spec=` argument of the search entry points: a `TPUSpec`
    passes through, ``"default"`` is the published constants of the chip
    this runs on (`repro.platform.device_spec`), and ``"measured"`` is this
    backend's calibrated spec from the autotune cache (`repro.tune`),
    auto-calibrating on a cache miss."""
    if isinstance(spec, TPUSpec):
        return spec
    from ..tune import resolve_spec as _tune_resolve  # deferred: tune -> pms

    return _tune_resolve(spec)


def _count_configs(kernel: str, n: int, *, sharded: bool = False) -> None:
    """Account every configuration the search actually priced in
    `obs.metrics` (``pms.configs_evaluated``) — the parity tests assert this
    stays at zero on a warm autotune-cache hit."""
    from ..obs import metrics as _metrics  # deferred: keep core leaf-light

    _metrics.counter(
        "pms.configs_evaluated", kernel=kernel, sharded=str(sharded).lower()
    ).inc(n)
    _metrics.counter(
        "pms.searches", kernel=kernel, sharded=str(sharded).lower()
    ).inc()


def tile_bytes(
    nblocks: int,
    fills: dict[str, int],
    *,
    blk: int,
    tile_i: int,
    in_tiles: Sequence[int],
    in_lanes: Sequence[int],
    out_lanes: int,
    remapper: RemapperConfig,
) -> tuple[int, int, int]:
    """HBM bytes of a kernel's walk over a layout, as (stream, input-factor
    tiles, accumulator tile): every grid step streams one block (a value and
    one local index per mode for each of `blk` slots, element widths from the
    Remapper configuration), each fill of input mode n moves a
    (in_tiles[n], in_lanes[n]) factor tile and each fill of "A" a
    (tile_i, out_lanes) accumulator tile.  `fills` are
    `BlockPlan.tile_fills` counts; the lane widths are the ones the kernel
    pads to."""
    r = remapper
    stream = nblocks * blk * (r.value_bytes + (len(in_tiles) + 1) * r.index_bytes)
    factor = sum(
        fills[chr(ord("B") + n)] * t * w
        for n, (t, w) in enumerate(zip(in_tiles, in_lanes))
    ) * r.value_bytes
    out = fills["A"] * tile_i * out_lanes * r.value_bytes
    return stream, factor, out


def kernel_fetch_bytes(
    plan: BlockPlan,
    in_lanes: Sequence[int],
    out_lanes: int,
    remapper: RemapperConfig,
    chunk: int,
) -> int:
    """HBM bytes the block specs of one kernel call over `plan` move
    (kernels/blocked.py): every stream block; an input-factor tile at each
    tile-id change; the accumulator tile, read and written at each change;
    and every tile afresh at the first step of each `chunk`-step call of a
    chunked grid (`blocked.chunk_blocks`)."""
    stream, factor, out = tile_bytes(
        plan.nblocks, plan.tile_fills(chunk=chunk), blk=plan.blk,
        tile_i=plan.tile_i, in_tiles=plan.in_tiles, in_lanes=in_lanes,
        out_lanes=out_lanes, remapper=remapper,
    )
    return stream + factor + 2 * out


def _kernel_times(
    cfg: MemoryControllerConfig,
    rank: int,
    nblocks: int,
    fills: dict[str, int],
    spec: TPUSpec,
    n_in: int = 2,
    *,
    tile_i: int | None = None,
    in_tiles: tuple[int, ...] | None = None,
    blk: int | None = None,
) -> tuple[float, float, float, float]:
    """Roofline terms.  Tile/block geometry defaults to the controller
    configuration; predict_from_plan overrides it with the *plan's* measured
    geometry so 'exact' estimates stay exact when a plan was built with
    different tiles than cfg describes."""
    rp = _rank_padded(rank)
    c = cfg.cache
    tile_i = c.tile_i if tile_i is None else tile_i
    in_tiles = c.input_tiles(n_in) if in_tiles is None else in_tiles
    blk = cfg.dma.blk if blk is None else blk
    stream_bytes, factor_bytes, out_bytes = tile_bytes(
        nblocks, fills, blk=blk, tile_i=tile_i, in_tiles=in_tiles,
        in_lanes=(rp,) * n_in, out_lanes=rp, remapper=cfg.remapper,
    )
    # one-hot segment matmul (TI x blk)@(blk x Rp) + hadamard/gather vector
    # work (one multiply+gather pair per input mode)
    flops = nblocks * (2 * tile_i * blk * rp + (2 + 2 * n_in) * blk * rp)
    return (
        stream_bytes / spec.hbm_bw,
        factor_bytes / spec.hbm_bw,
        out_bytes / spec.hbm_bw,
        flops / spec.peak_flops_f32,
    )


def predict_from_plan(plan: BlockPlan, rank: int, cfg: MemoryControllerConfig, spec: TPUSpec = TPUSpec()) -> PMSEstimate:
    """Exact PMS terms from a built memory layout (measured fills/padding)."""
    fills = plan.tile_fills()
    n_in = plan.n_in
    ts, tf, to, tc = _kernel_times(
        cfg, rank, plan.nblocks, fills, spec, n_in=n_in,
        tile_i=plan.tile_i, in_tiles=plan.in_tiles, blk=plan.blk,
    )
    return PMSEstimate(
        cfg=cfg,
        t_stream=ts,
        t_factor=tf,
        t_out=to,
        t_compute=tc,
        vmem_bytes=cfg.vmem_bytes(_rank_padded(rank), n_in=n_in),
        nblocks=plan.nblocks,
        padding_fraction=plan.padding_fraction(),
    )


def _ttmc_kernel_times(
    cfg: MemoryControllerConfig,
    in_ranks: tuple[int, ...],
    nblocks: int,
    fills: dict[str, int],
    spec: TPUSpec,
    *,
    tile_i: int | None = None,
    in_tiles: tuple[int, ...] | None = None,
    blk: int | None = None,
) -> tuple[float, float, float, float]:
    """Roofline terms for the TTM-chain kernel.  Same stream model as MTTKRP
    (the BlockPlan layout is shared); the factor term pays each input mode's
    own lane padding, the output term pays the core-tensor slice width
    Pp = cols_padded(prod(in_ranks)), and compute adds the Kronecker-chain
    widening (one (blk, P_k) elementwise multiply per input mode) on top of
    the one-hot segment matmul."""
    n_in = len(in_ranks)
    pp = _rank_padded(math.prod(in_ranks))
    c = cfg.cache
    tile_i = c.tile_i if tile_i is None else tile_i
    in_tiles = c.input_tiles(n_in) if in_tiles is None else in_tiles
    blk = cfg.dma.blk if blk is None else blk
    stream_bytes, factor_bytes, out_bytes = tile_bytes(
        nblocks, fills, blk=blk, tile_i=tile_i, in_tiles=in_tiles,
        in_lanes=tuple(_rank_padded(rk) for rk in in_ranks), out_lanes=pp,
        remapper=cfg.remapper,
    )
    # Kronecker chain: after input mode k the per-element row is prod(R_1..R_k)
    # wide; each widening step is one multiply per produced element (+ the
    # gather), then the one-hot segment matmul runs at the padded width.
    widen = 0
    p_k = 1
    for rk in in_ranks:
        p_k *= rk
        widen += 2 * p_k
    flops = nblocks * (2 * tile_i * blk * pp + blk * widen)
    return (
        stream_bytes / spec.hbm_bw,
        factor_bytes / spec.hbm_bw,
        out_bytes / spec.hbm_bw,
        flops / spec.peak_flops_f32,
    )


def _ttmc_in_ranks(core_ranks: Sequence[int], mode: int) -> tuple[int, ...]:
    return tuple(int(r) for m, r in enumerate(core_ranks) if m != mode)


def _ttmc_vmem(cfg: MemoryControllerConfig, in_ranks: tuple[int, ...]) -> int:
    return cfg.vmem_bytes_ttmc(
        _rank_padded(math.prod(in_ranks)), tuple(_rank_padded(r) for r in in_ranks)
    )


def predict_ttmc(
    plan: BlockPlan,
    core_ranks: Sequence[int],
    cfg: MemoryControllerConfig,
    spec: TPUSpec = TPUSpec(),
) -> PMSEstimate:
    """Exact PMS terms for the TTM-chain kernel from a built memory layout
    (measured fills/padding; the layout is the same one MTTKRP uses)."""
    in_ranks = tuple(int(core_ranks[m]) for m in plan.in_modes)
    fills = plan.tile_fills()
    ts, tf, to, tc = _ttmc_kernel_times(
        cfg, in_ranks, plan.nblocks, fills, spec,
        tile_i=plan.tile_i, in_tiles=plan.in_tiles, blk=plan.blk,
    )
    return PMSEstimate(
        cfg=cfg,
        t_stream=ts,
        t_factor=tf,
        t_out=to,
        t_compute=tc,
        vmem_bytes=_ttmc_vmem(cfg, in_ranks),
        nblocks=plan.nblocks,
        padding_fraction=plan.padding_fraction(),
    )


def _expected_occupied(bins: float, balls: float) -> float:
    """E[# occupied bins] for `balls` uniform balls in `bins` bins."""
    if bins <= 1:
        return 1.0
    return bins * (1.0 - math.exp(-balls / bins))


def _analytic_layout(
    hs: HypergraphStats, mode: int, cfg: MemoryControllerConfig
) -> tuple[int, dict[str, int], float]:
    """Balls-in-bins occupancy estimate of the BlockPlan geometry — shared by
    the MTTKRP and TTMc analytic predictors (the group structure depends only
    on the layout, not the kernel).  Returns (nblocks, fills, padding)."""
    in_modes = [m for m in range(hs.nmodes) if m != mode]
    n_in = len(in_modes)
    c, d = cfg.cache, cfg.dma
    in_tiles = c.input_tiles(n_in)
    n_it = math.ceil(hs.shape[mode] / c.tile_i)
    n_ins = [math.ceil(hs.shape[m] / t) for m, t in zip(in_modes, in_tiles)]

    groups = _expected_occupied(n_it * math.prod(n_ins), hs.nnz)
    # each occupied tile-id group costs >= 1 block; remaining nnz fill blocks
    nblocks = int(groups + hs.nnz / d.blk)
    fills = {"A": _expected_occupied(n_it, hs.nnz)}
    for n in range(n_in):
        fills[chr(ord("B") + n)] = groups  # each id changes at most once/group
    fills = {k: int(max(1, v)) for k, v in fills.items()}
    padding = max(0.0, 1.0 - hs.nnz / float(nblocks * d.blk))
    return nblocks, fills, padding


def predict_ttmc_analytic(
    hs: HypergraphStats,
    mode: int,
    core_ranks: Sequence[int],
    cfg: MemoryControllerConfig,
    spec: TPUSpec = TPUSpec(),
) -> PMSEstimate:
    """Analytic TTMc PMS: the shared occupancy model (`_analytic_layout`)
    with TTMc roofline terms."""
    in_ranks = _ttmc_in_ranks(core_ranks, mode)
    nblocks, fills, padding = _analytic_layout(hs, mode, cfg)
    ts, tf, to, tc = _ttmc_kernel_times(cfg, in_ranks, nblocks, fills, spec)
    return PMSEstimate(
        cfg=cfg,
        t_stream=ts,
        t_factor=tf,
        t_out=to,
        t_compute=tc,
        vmem_bytes=_ttmc_vmem(cfg, in_ranks),
        nblocks=nblocks,
        padding_fraction=padding,
    )


def _tt_pairs(
    core_ranks: Sequence[int], nmodes: int, mode: int
) -> tuple[tuple[tuple[int, int], ...], tuple[int, int]]:
    """Per-core (rl, rr) bond pairs from the N-1 interior TT ranks, split
    into the input pairs (ascending in_modes order — the first `mode` of
    them chain from the left) and the output mode's own pair."""
    tr = tuple(int(r) for r in core_ranks)
    bounds = (1,) + tr + (1,)
    pairs = tuple((bounds[k], bounds[k + 1]) for k in range(nmodes))
    in_pairs = tuple(p for m, p in enumerate(pairs) if m != mode)
    return in_pairs, pairs[mode]


def _tt_iface_cols(in_pairs: tuple[tuple[int, int], ...]) -> int:
    """Live columns of the two interface-chain scratch vectors: the kernel
    keeps the left and the right chain each lane-padded to the widest bond
    (kernels/tt_pallas.py)."""
    return 2 * _rank_padded(max(max(p) for p in in_pairs))


def _tt_vmem(
    cfg: MemoryControllerConfig,
    in_pairs: tuple[tuple[int, int], ...],
    out_pair: tuple[int, int],
) -> int:
    return cfg.vmem_bytes_tt(
        _rank_padded(out_pair[0] * out_pair[1]),
        tuple(_rank_padded(a * b) for a, b in in_pairs),
        _tt_iface_cols(in_pairs),
    )


def _tt_kernel_times(
    cfg: MemoryControllerConfig,
    in_pairs: tuple[tuple[int, int], ...],
    out_pair: tuple[int, int],
    n_left: int,
    nblocks: int,
    fills: dict[str, int],
    spec: TPUSpec,
    *,
    tile_i: int | None = None,
    in_tiles: tuple[int, ...] | None = None,
    blk: int | None = None,
) -> tuple[float, float, float, float]:
    """Roofline terms for the TT-core kernel.  Same stream model as MTTKRP /
    TTMc (the BlockPlan layout is shared); the factor term pays each core
    interface's own lane padding rank_padded(rl_k*rr_k), the output term the
    rank_padded(rl_m*rr_m) accumulator width, and compute replaces the
    Kronecker-chain widening with the two interface chains (one (rl, rr)
    matrix-vector product per input core) plus the final Kronecker of two."""
    n_in = len(in_pairs)
    out_cols = out_pair[0] * out_pair[1]
    pp = _rank_padded(out_cols)
    c = cfg.cache
    tile_i = c.tile_i if tile_i is None else tile_i
    in_tiles = c.input_tiles(n_in) if in_tiles is None else in_tiles
    blk = cfg.dma.blk if blk is None else blk
    stream_bytes, factor_bytes, out_bytes = tile_bytes(
        nblocks, fills, blk=blk, tile_i=tile_i, in_tiles=in_tiles,
        in_lanes=tuple(_rank_padded(a * b) for a, b in in_pairs), out_lanes=pp,
        remapper=cfg.remapper,
    )
    # Interface chains: folding core k into a chain vector is a (rl_k, rr_k)
    # matrix-vector product (2*rl*rr flops per element); the Kronecker of
    # the two finished interfaces plus the value scale adds 2*out_cols; the
    # one-hot segment matmul then runs at the padded width.
    chain = sum(2 * a * b for a, b in in_pairs) + 2 * out_cols
    flops = nblocks * (2 * tile_i * blk * pp + blk * chain)
    return (
        stream_bytes / spec.hbm_bw,
        factor_bytes / spec.hbm_bw,
        out_bytes / spec.hbm_bw,
        flops / spec.peak_flops_f32,
    )


def predict_tt(
    plan: BlockPlan,
    core_ranks: Sequence[int],
    cfg: MemoryControllerConfig,
    spec: TPUSpec = TPUSpec(),
) -> PMSEstimate:
    """Exact PMS terms for the TT-core kernel from a built memory layout
    (measured fills/padding; the layout is the same one MTTKRP uses).
    `core_ranks` are the N-1 INTERIOR TT bond ranks."""
    nmodes = plan.n_in + 1
    in_pairs, out_pair = _tt_pairs(core_ranks, nmodes, plan.mode)
    n_left = plan.mode
    fills = plan.tile_fills()
    ts, tf, to, tc = _tt_kernel_times(
        cfg, in_pairs, out_pair, n_left, plan.nblocks, fills, spec,
        tile_i=plan.tile_i, in_tiles=plan.in_tiles, blk=plan.blk,
    )
    return PMSEstimate(
        cfg=cfg,
        t_stream=ts,
        t_factor=tf,
        t_out=to,
        t_compute=tc,
        vmem_bytes=_tt_vmem(cfg, in_pairs, out_pair),
        nblocks=plan.nblocks,
        padding_fraction=plan.padding_fraction(),
    )


def predict_tt_analytic(
    hs: HypergraphStats,
    mode: int,
    core_ranks: Sequence[int],
    cfg: MemoryControllerConfig,
    spec: TPUSpec = TPUSpec(),
) -> PMSEstimate:
    """Analytic TT-core PMS: the shared occupancy model (`_analytic_layout`)
    with TT roofline terms.  `core_ranks` are the N-1 interior TT ranks."""
    in_pairs, out_pair = _tt_pairs(core_ranks, hs.nmodes, mode)
    n_left = mode
    nblocks, fills, padding = _analytic_layout(hs, mode, cfg)
    ts, tf, to, tc = _tt_kernel_times(
        cfg, in_pairs, out_pair, n_left, nblocks, fills, spec
    )
    return PMSEstimate(
        cfg=cfg,
        t_stream=ts,
        t_factor=tf,
        t_out=to,
        t_compute=tc,
        vmem_bytes=_tt_vmem(cfg, in_pairs, out_pair),
        nblocks=nblocks,
        padding_fraction=padding,
    )


def predict_analytic(
    hs: HypergraphStats,
    mode: int,
    rank: int,
    cfg: MemoryControllerConfig,
    spec: TPUSpec = TPUSpec(),
) -> PMSEstimate:
    """Analytic PMS: no plan construction.  Estimates group structure with a
    balls-in-bins occupancy model (skew makes it conservative: skewed tensors
    have fewer, hotter groups, i.e. fewer fills than predicted)."""
    n_in = hs.nmodes - 1
    nblocks, fills, padding = _analytic_layout(hs, mode, cfg)
    ts, tf, to, tc = _kernel_times(cfg, rank, nblocks, fills, spec, n_in=n_in)
    return PMSEstimate(
        cfg=cfg,
        t_stream=ts,
        t_factor=tf,
        t_out=to,
        t_compute=tc,
        vmem_bytes=cfg.vmem_bytes(_rank_padded(rank), n_in=n_in),
        nblocks=nblocks,
        padding_fraction=padding,
    )


DEFAULT_TILE_CHOICES: tuple[int, ...] = (128, 256, 512, 1024)
DEFAULT_BLK_CHOICES: tuple[int, ...] = (128, 256, 512, 1024)


def _validate_kernel_args(kernel: str, core_ranks, nmodes: int) -> None:
    """Shared argument contract of every per-kernel PMS entry point."""
    if kernel not in ("mttkrp", "ttmc", "tt"):
        raise ValueError(
            f"unknown kernel {kernel!r}: expected 'mttkrp', 'ttmc' or 'tt'"
        )
    if kernel == "ttmc":
        if core_ranks is None:
            raise ValueError("kernel='ttmc' requires core_ranks (the full N-tuple)")
        if len(core_ranks) != nmodes:
            raise ValueError(
                f"core_ranks has {len(core_ranks)} entries for a "
                f"{nmodes}-mode tensor (pass the full N-tuple, not the "
                f"N-1 input ranks)"
            )
    if kernel == "tt":
        if core_ranks is None:
            raise ValueError(
                "kernel='tt' requires core_ranks (the N-1 interior TT ranks)"
            )
        if len(core_ranks) != nmodes - 1:
            raise ValueError(
                f"core_ranks has {len(core_ranks)} entries for a "
                f"{nmodes}-mode tensor (pass the N-1 interior TT ranks, "
                f"not per-mode ranks)"
            )


def _search_kernel_ranks(kernel: str, core_ranks, nmodes: int, mode: int):
    """The kernel-specific rank payload `_feasible_configs` consumes: TTMc's
    input-rank tuple, TT's `(in_pairs, out_pair, n_left)` triple (n_left ==
    mode: plan.in_modes is ascending), None for MTTKRP."""
    if kernel == "ttmc":
        return _ttmc_in_ranks(core_ranks, mode)
    if kernel == "tt":
        in_pairs, out_pair = _tt_pairs(core_ranks, nmodes, mode)
        return (in_pairs, out_pair, mode)
    return None


def _feasible_configs(
    n_in: int,
    rank: int,
    spec: TPUSpec,
    tile_choices: Sequence[int],
    blk_choices: Sequence[int],
    kernel: str,
    kernel_ranks,
):
    """The one enumeration of the controller design space, pruned by the
    per-kernel VMEM-fit constraint — `search` and `search_sharded` both
    consume this, so they always explore the identical candidate grid.
    `kernel_ranks` is the kernel-specific rank payload: the input-rank tuple
    for 'ttmc', the `(in_pairs, out_pair, n_left)` triple for 'tt', unused
    for 'mttkrp'."""
    for ti, tj, tk, blk in itertools.product(
        tile_choices, tile_choices, tile_choices, blk_choices
    ):
        cfg = MemoryControllerConfig(
            cache=CacheEngineConfig(tile_i=ti, tile_j=tj, tile_k=tk),
            dma=DMAEngineConfig(blk=blk),
        )
        if kernel == "ttmc":
            in_ranks = kernel_ranks
            fits = cfg.fits_ttmc(
                spec,
                _rank_padded(math.prod(in_ranks)),
                tuple(_rank_padded(r) for r in in_ranks),
            )
        elif kernel == "tt":
            in_pairs, out_pair, _ = kernel_ranks
            fits = cfg.fits_tt(
                spec,
                _rank_padded(out_pair[0] * out_pair[1]),
                tuple(_rank_padded(a * b) for a, b in in_pairs),
                _tt_iface_cols(in_pairs),
            )
        else:
            fits = cfg.fits(spec, _rank_padded(rank), n_in=n_in)
        if fits:
            yield cfg


def search(
    st_or_stats: SparseTensor | HypergraphStats,
    mode: int,
    rank: int,
    *,
    spec: TPUSpec = TPUSpec(),
    tile_choices: Sequence[int] = DEFAULT_TILE_CHOICES,
    blk_choices: Sequence[int] = DEFAULT_BLK_CHOICES,
    exact: bool = False,
    top_k: int = 5,
    kernel: str = "mttkrp",
    core_ranks: Sequence[int] | None = None,
) -> list[PMSEstimate]:
    """Exhaustive module-by-module parameter search (paper Sec. 5.3), pruned
    by the VMEM-fit constraint.  exact=True builds a BlockPlan per candidate
    (accurate, slower) — use for final configuration of a dataset domain.

    kernel: 'mttkrp' (CP-ALS, scored at `rank`), 'ttmc' (Tucker HOOI,
    scored at `core_ranks` — the full N-tuple; `rank` is ignored) or 'tt'
    (TT-ALS, scored at `core_ranks` — the N-1 interior TT bond ranks).  The
    search tunes the controller *per kernel*: TTMc's core-tensor output tile,
    TT's two-interface scratch, and the per-factor lane paddings change both
    the VMEM constraint and the roofline, so the best configuration generally
    differs between kernels."""
    spec = resolve_spec(spec)
    if isinstance(st_or_stats, SparseTensor):
        hs = hg_stats(st_or_stats)
        st = st_or_stats
    else:
        hs, st = st_or_stats, None
        exact = False
    _validate_kernel_args(kernel, core_ranks, hs.nmodes)
    n_in = hs.nmodes - 1
    kernel_ranks = _search_kernel_ranks(kernel, core_ranks, hs.nmodes, mode)

    results: list[PMSEstimate] = []
    for cfg in _feasible_configs(
        n_in, rank, spec, tile_choices, blk_choices, kernel, kernel_ranks
    ):
        if exact and st is not None:
            plan = plan_blocks(
                st, mode, tile_i=cfg.cache.tile_i, blk=cfg.dma.blk,
                in_tiles=cfg.cache.input_tiles(n_in),
            )
            if kernel == "ttmc":
                results.append(predict_ttmc(plan, core_ranks, cfg, spec))
            elif kernel == "tt":
                results.append(predict_tt(plan, core_ranks, cfg, spec))
            else:
                results.append(predict_from_plan(plan, rank, cfg, spec))
        elif kernel == "ttmc":
            results.append(predict_ttmc_analytic(hs, mode, core_ranks, cfg, spec))
        elif kernel == "tt":
            results.append(predict_tt_analytic(hs, mode, core_ranks, cfg, spec))
        else:
            results.append(predict_analytic(hs, mode, rank, cfg, spec))
    _count_configs(kernel, len(results))
    results.sort(key=lambda e: e.t_total)
    return results[:top_k]


# ---------------------------------------------------------------------------
# Sharded PMS: score a configuration by its worst shard (parallel makespan)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedPMSEstimate:
    """PMS estimate for the distributed planned path: the stream is
    partitioned into `nshards` balanced output-tile ranges
    (dist/sharding.partition_stream) and every shard runs the kernel on its
    own device, so wall-clock is the *makespan* — the slowest shard, not the
    sum.  `t_total` therefore reports max over shards; the collective's
    `I_out*R` all-reduce is shared by every configuration of the same rank
    and does not reorder candidates, so it is not modeled here."""

    cfg: MemoryControllerConfig
    per_shard: tuple[PMSEstimate, ...]
    shard_nnz: tuple[int, ...]

    @property
    def nshards(self) -> int:
        return len(self.per_shard)

    @property
    def t_total(self) -> float:
        """Parallel makespan: the slowest shard's roofline time."""
        return max(e.t_total for e in self.per_shard)

    @property
    def critical_shard(self) -> int:
        """Index of the shard that sets the makespan."""
        ts = [e.t_total for e in self.per_shard]
        return ts.index(max(ts))

    @property
    def vmem_bytes(self) -> int:
        """Per-device VMEM footprint (identical across shards: one cfg)."""
        return self.per_shard[0].vmem_bytes

    @property
    def imbalance(self) -> float:
        """max / mean shard nnz (1.0 = perfectly balanced partition)."""
        from ..dist.sharding import stream_imbalance

        return stream_imbalance(self.shard_nnz)

    @property
    def bottleneck(self) -> str:
        return self.per_shard[self.critical_shard].bottleneck


def _empty_shard_estimate(
    cfg: MemoryControllerConfig,
    rank: int,
    n_in: int,
    kernel: str,
    kernel_ranks,
) -> PMSEstimate:
    """Zero-cost estimate for a shard that owns no non-zeros (its kernel
    streams one all-padding block; negligible against any real shard)."""
    if kernel == "ttmc":
        vmem = _ttmc_vmem(cfg, kernel_ranks)
    elif kernel == "tt":
        vmem = _tt_vmem(cfg, *kernel_ranks[:2])
    else:
        vmem = cfg.vmem_bytes(_rank_padded(rank), n_in=n_in)
    return PMSEstimate(
        cfg=cfg, t_stream=0.0, t_factor=0.0, t_out=0.0, t_compute=0.0,
        vmem_bytes=vmem, nblocks=0, padding_fraction=0.0,
    )


def _shard_estimate(
    shard: SparseTensor,
    hs: HypergraphStats | None,
    mode: int,
    rank: int,
    cfg: MemoryControllerConfig,
    spec: TPUSpec,
    kernel: str,
    core_ranks: Sequence[int] | None,
    exact: bool,
) -> PMSEstimate:
    n_in = shard.nmodes - 1
    if shard.nnz == 0:
        kernel_ranks = _search_kernel_ranks(kernel, core_ranks, shard.nmodes, mode)
        return _empty_shard_estimate(cfg, rank, n_in, kernel, kernel_ranks)
    if exact:
        plan = plan_blocks(
            shard, mode, tile_i=cfg.cache.tile_i, blk=cfg.dma.blk,
            in_tiles=cfg.cache.input_tiles(n_in),
        )
        if kernel == "ttmc":
            return predict_ttmc(plan, core_ranks, cfg, spec)
        if kernel == "tt":
            return predict_tt(plan, core_ranks, cfg, spec)
        return predict_from_plan(plan, rank, cfg, spec)
    hs = hs if hs is not None else hg_stats(shard)
    if kernel == "ttmc":
        return predict_ttmc_analytic(hs, mode, core_ranks, cfg, spec)
    if kernel == "tt":
        return predict_tt_analytic(hs, mode, core_ranks, cfg, spec)
    return predict_analytic(hs, mode, rank, cfg, spec)


def predict_sharded(
    st: SparseTensor,
    mode: int,
    rank: int,
    nshards: int,
    cfg: MemoryControllerConfig,
    *,
    spec: TPUSpec = TPUSpec(),
    kernel: str = "mttkrp",
    core_ranks: Sequence[int] | None = None,
    exact: bool = True,
) -> ShardedPMSEstimate:
    """PMS terms for one configuration of the sharded planned path: the
    stream is partitioned exactly as the workspace builder partitions it
    (balanced nnz, tile_i-aligned) and each shard is scored independently —
    exact=True builds every shard's BlockPlan (measured fills), exact=False
    uses the analytic occupancy model per shard (conservative: it spreads
    each shard's nnz over the *global* tile space, overestimating fills)."""
    _validate_kernel_args(kernel, core_ranks, st.nmodes)
    from ..dist.sharding import partition_stream

    part = partition_stream(st, mode, nshards, tile=cfg.cache.tile_i)
    ests = tuple(
        _shard_estimate(sh, None, mode, rank, cfg, spec, kernel, core_ranks, exact)
        for sh in part.shards
    )
    return ShardedPMSEstimate(cfg=cfg, per_shard=ests, shard_nnz=part.shard_nnz)


def search_sharded(
    st: SparseTensor,
    mode: int,
    rank: int,
    nshards: int,
    *,
    spec: TPUSpec = TPUSpec(),
    tile_choices: Sequence[int] = DEFAULT_TILE_CHOICES,
    blk_choices: Sequence[int] = DEFAULT_BLK_CHOICES,
    exact: bool = False,
    top_k: int = 5,
    kernel: str = "mttkrp",
    core_ranks: Sequence[int] | None = None,
) -> list[ShardedPMSEstimate]:
    """`search`, distributed: rank every VMEM-feasible configuration by the
    time of its *worst shard* — a configuration that wins on the balanced
    average can lose on the critical shard, and the critical shard is what
    the shard_map sweep waits for (the makespan).  Partitions (and per-shard
    hypergraph stats) are cached per tile_i, since the split depends only on
    the output tile granularity."""
    spec = resolve_spec(spec)
    _validate_kernel_args(kernel, core_ranks, st.nmodes)
    from ..dist.sharding import partition_stream

    n_in = st.nmodes - 1
    kernel_ranks = _search_kernel_ranks(kernel, core_ranks, st.nmodes, mode)
    parts: dict[int, tuple] = {}  # tile_i -> (partition, per-shard stats)
    results: list[ShardedPMSEstimate] = []
    for cfg in _feasible_configs(
        n_in, rank, spec, tile_choices, blk_choices, kernel, kernel_ranks
    ):
        ti = cfg.cache.tile_i
        if ti not in parts:
            part = partition_stream(st, mode, nshards, tile=ti)
            sstats = [hg_stats(s) if s.nnz else None for s in part.shards]
            parts[ti] = (part, sstats)
        part, sstats = parts[ti]
        ests = tuple(
            _shard_estimate(sh, hs, mode, rank, cfg, spec, kernel, core_ranks, exact)
            for sh, hs in zip(part.shards, sstats)
        )
        results.append(
            ShardedPMSEstimate(cfg=cfg, per_shard=ests, shard_nnz=part.shard_nnz)
        )
    _count_configs(kernel, len(results), sharded=True)
    results.sort(key=lambda e: e.t_total)
    return results[:top_k]
