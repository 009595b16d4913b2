"""Jit'd wrappers for the decomposition kernels: plan construction + padding +
dispatch between the Pallas kernels and the pure-JAX references.

Three kernel families share the BlockPlan substrate (the memory controller is
*programmable*, not MTTKRP-specific):
  * MTTKRP  — `PlannedMTTKRP` / `mttkrp_auto` / `PlannedCPALS` (CP-ALS,
              paper Alg. 1 + Alg. 5);
  * TTMc    — `PlannedTTMC` / `tucker_auto` (sparse Tucker HOOI; see
              repro.tucker).  Same remapped layout, Kronecker-chain compute.
  * TT-core — `PlannedTTCore` / `tt_auto` (tensor-train ALS; see repro.tt).
              Same remapped layout, Kronecker-of-two-interfaces compute.

`PlannedCPALS` is the workspace that makes the Pallas kernel the *production*
decomposition path (paper Alg. 1 + Alg. 5): one PMS-tunable BlockPlan +
device-resident layout per output mode, built once and cached across every
ALS iteration (the paper's layout="copies" posture — per-mode remapped
copies, a legitimate space/time trade on HBM).  `PlannedTucker`
(repro.tucker.hooi) and `PlannedTT` (repro.tt.als) mirror it for the HOOI
and TT-ALS loops.  Everything the workspaces share — padding, residency,
plan-byte accounting, the lazily-built sweep, the drive loop — lives in
`repro.kernels.workspace.PlannedWorkspace`; the classes here supply only
their format's sweep body.

The one-shot dispatchers share a keyed LRU plan cache.  The key leads with a
kernel-kind discriminator ("mttkrp" / "ttmc" / "tt"): two kernels sharing a
tensor fingerprint + mode + rank must never silently reuse each other's
plans (the layouts coincide today, but the cached objects carry
kernel-specific state).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from collections import OrderedDict
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.coo import SparseTensor
from ..core.cp_als import _update_mode, fit_value, inner_with_model, model_norm_sq
from ..core.memctrl import MemoryControllerConfig, TPUSpec
from ..core.pms import (
    predict_from_plan,
    resolve_spec as pms_resolve_spec,
    search as pms_search,
)
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..core.remap import BlockPlan, plan_blocks, plans_validated, validate_plan
from ..core.mttkrp import mttkrp as mttkrp_jax
from .mttkrp_pallas import mttkrp_pallas_call, pad_factor, rank_padded
from .ref import ttcore_ref, ttmc_ref
from .tt_pallas import tt_out_cols, tt_out_pair, ttcore_pallas_call
from .ttm_pallas import kron_cols, ttmc_pallas_call
from .workspace import (
    PlannedWorkspace,
    ShardedWorkspace,
    _padded_rows_from,
    _plan_device_arrays,
    planned_layout_bytes,
    sharded_layout_bytes,
    sweep_scope,
)

__all__ = [
    "PlannedMTTKRP",
    "make_planned_mttkrp",
    "PlannedCPALS",
    "make_planned_cp_als",
    "PlannedTTMC",
    "make_planned_ttmc",
    "PlannedTTCore",
    "make_planned_ttcore",
    "mttkrp_auto",
    "tucker_auto",
    "tt_auto",
    "plan_cache_stats",
    "plan_cache_clear",
    "planned_padded_rows",
    "planned_layout_bytes",
    "ShardedPlannedMTTKRP",
    "ShardedPlannedCPALS",
    "ShardedPlannedTucker",
    "ShardedPlannedTT",
    "make_sharded_planned_mttkrp",
    "make_sharded_planned_cp_als",
    "make_sharded_planned_tucker",
    "make_sharded_planned_tt",
]


def planned_padded_rows(ops: dict[int, "PlannedMTTKRP | PlannedTTMC"], nmodes: int) -> tuple[int, ...]:
    """Device-resident row padding per mode for a per-mode plan family: the
    largest padding any plan requires of that factor (its own plan's
    out_rows, plus in_rows wherever it appears as an input mode).  Each
    plan's kernel slices the rows it needs — a static, zero-copy slice
    inside a sweep jit."""
    return _padded_rows_from({m: op.plan for m, op in ops.items()}, nmodes)


@dataclasses.dataclass
class PlannedMTTKRP:
    """A compiled memory-controller instance for one (tensor, mode): the
    device-resident BlockPlan layout + a callable running the Pallas kernel."""

    plan: BlockPlan
    rank: int
    cfg: MemoryControllerConfig = dataclasses.field(
        default_factory=MemoryControllerConfig
    )
    layout: tuple = dataclasses.field(default=(), init=False, repr=False)

    def __post_init__(self):
        self.layout = _plan_device_arrays(self.plan)

    @property
    def out_cols(self) -> int:
        return self.rank

    def __call__(self, *in_factors: jax.Array) -> jax.Array:
        """Factors for the N-1 *input* modes (plan.in_modes order).
        Returns (out_rows_unpadded, rank)."""
        p = self.plan
        assert len(in_factors) == p.n_in
        rp = rank_padded(self.rank)
        pads = tuple(
            pad_factor(f, rows, rp) for f, rows in zip(in_factors, p.in_rows)
        )
        out = mttkrp_pallas_call(
            *self.layout,
            pads,
            tile_i=p.tile_i,
            in_tiles=p.in_tiles,
            out_rows=p.out_rows,
        )
        return out[: p.out_rows, : self.rank]

    def output(self, factors: Sequence[jax.Array], true_rows: int) -> jax.Array:
        return self(*(factors[m] for m in self.plan.in_modes))[:true_rows]


def _resolve_tune(auto_tune, spec):
    """Normalize the (auto_tune, spec) pair every planned builder accepts:
    `auto_tune` must be False / True / "cached" ("cached" = True semantics
    with the winning configuration persisted in `repro.tune.cache`, so a
    warm cache skips the PMS sweep entirely); `spec` may be a TPUSpec,
    "default", or "measured" (this backend's calibrated spec)."""
    if auto_tune not in (False, True, "cached"):
        raise ValueError(
            f"auto_tune must be False, True or 'cached', got {auto_tune!r}"
        )
    return auto_tune, pms_resolve_spec(spec)


def _searched_cfg(
    auto_tune, kind: str, st: SparseTensor, mode: int, rank_key, spec, search,
    *, nshards: int | None = None,
) -> MemoryControllerConfig:
    """Run (or skip) the PMS sweep per the auto_tune policy: True runs
    `search()` every call; "cached" serves the persisted winner for this
    (kind, tensor, mode, rank payload, backend, spec, shards) key and only
    searches — then writes back — on a miss."""
    if auto_tune == "cached":
        from ..tune.cache import cached_config  # deferred: tune -> ops

        return cached_config(
            kind, st.fingerprint(), mode, rank_key, spec, search, nshards=nshards
        )
    return search()


def make_planned_mttkrp(
    st: SparseTensor,
    mode: int,
    rank: int,
    *,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> PlannedMTTKRP:
    """Build the memory layout (Tensor Remapper) + kernel instance.  With
    auto_tune=True the PMS picks the controller parameters (Sec. 5.3);
    auto_tune="cached" additionally persists/reuses the winner on disk."""
    auto_tune, spec = _resolve_tune(auto_tune, spec)
    if auto_tune:
        def _search():
            best = pms_search(st, mode, rank, spec=spec, top_k=1)
            if not best:
                raise ValueError(
                    f"PMS found no VMEM-feasible controller configuration for "
                    f"mode {mode} at rank {rank} (spec budget "
                    f"{spec.vmem_bytes * spec.vmem_usable_frac:.0f} bytes)"
                )
            return best[0].cfg

        cfg = _searched_cfg(auto_tune, "mttkrp", st, mode, rank, spec, _search)
    cfg = cfg or MemoryControllerConfig()
    n_in = st.nmodes - 1
    plan = plan_blocks(
        st,
        mode,
        tile_i=cfg.cache.tile_i,
        blk=cfg.dma.blk,
        in_tiles=cfg.cache.input_tiles(n_in),
    )
    return PlannedMTTKRP(plan=plan, rank=rank, cfg=cfg)


@dataclasses.dataclass
class PlannedTTMC:
    """A compiled memory-controller instance of the TTM-chain kernel for one
    (tensor, output mode): the same device-resident BlockPlan layout as
    MTTKRP, driving the Kronecker-chain Pallas kernel (repro.tucker HOOI's
    per-mode contraction).  `in_ranks` are the input-factor ranks in
    plan.in_modes order; the output has prod(in_ranks) true columns."""

    plan: BlockPlan
    in_ranks: tuple[int, ...]
    cfg: MemoryControllerConfig = dataclasses.field(
        default_factory=MemoryControllerConfig
    )
    layout: tuple = dataclasses.field(default=(), init=False, repr=False)

    def __post_init__(self):
        self.in_ranks = tuple(int(r) for r in self.in_ranks)
        self.layout = _plan_device_arrays(self.plan)

    @property
    def out_cols(self) -> int:
        return kron_cols(self.in_ranks)

    def __call__(self, *in_factors: jax.Array) -> jax.Array:
        """Factors for the N-1 *input* modes (plan.in_modes order), true
        shapes.  Returns (out_rows_unpadded, prod(in_ranks))."""
        p = self.plan
        assert len(in_factors) == p.n_in
        pads = tuple(
            pad_factor(f, rows, rank_padded(r))
            for f, rows, r in zip(in_factors, p.in_rows, self.in_ranks)
        )
        out = self.call_padded(pads)
        return out[: p.out_rows, : self.out_cols]

    def call_padded(self, in_factors_pad: Sequence[jax.Array], layout=None) -> jax.Array:
        """Run the kernel on already row/lane-padded input factors (the
        PlannedTucker sweep path).  Returns the padded (out_rows, Pp) tile
        with unvisited output tiles zeroed.  Inside a jitted sweep pass this
        op's `layout` in as a traced argument: arrays a jitted function
        closes over are embedded in its program as constants."""
        p = self.plan
        layout = self.layout if layout is None else layout
        return ttmc_pallas_call(
            *layout,
            tuple(in_factors_pad),
            tile_i=p.tile_i,
            in_tiles=p.in_tiles,
            in_ranks=self.in_ranks,
            out_rows=p.out_rows,
        )

    def output(self, factors: Sequence[jax.Array], true_rows: int) -> jax.Array:
        return self(*(factors[m] for m in self.plan.in_modes))[:true_rows]


def make_planned_ttmc(
    st: SparseTensor,
    mode: int,
    core_ranks: Sequence[int],
    *,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> PlannedTTMC:
    """Build the memory layout + TTMc kernel instance for one output mode.

    Args:
      st: host-side COO tensor (>= 3 modes).
      mode: the output mode n — the kernel computes the unfolding
        Y_(n) = X_(n) (kron of the other factors).
      core_ranks: the FULL N-tuple of Tucker core ranks (not the N-1 input
        ranks); the instance's `in_ranks` are taken from it in
        plan.in_modes order.  Each input factor is lane-padded to its own
        `rank_padded(R_m)`; the output carries `prod(in_ranks)` true
        columns, lane-padded to `cols_padded(prod R_m)`.
      cfg / auto_tune / spec: controller configuration, or let the PMS tune
        it for the TTMc kernel specifically (the core-tensor output tile
        changes both the VMEM constraint and the roofline).

    Returns:
      A `PlannedTTMC` holding the device-resident BlockPlan layout — the
      SAME layout `make_planned_mttkrp` would build for this (tensor, mode,
      cfg); only the kernel differs.  Invariant: `op(*in_factors)` expects
      true-shape factors for plan.in_modes in order and returns
      (I_mode, prod(in_ranks))."""
    core_ranks = tuple(int(r) for r in core_ranks)
    if len(core_ranks) != st.nmodes:
        raise ValueError(
            f"core_ranks has {len(core_ranks)} entries for a "
            f"{st.nmodes}-mode tensor (pass the full N-tuple)"
        )
    auto_tune, spec = _resolve_tune(auto_tune, spec)
    if auto_tune:
        def _search():
            best = pms_search(
                st, mode, max(core_ranks), spec=spec, top_k=1,
                kernel="ttmc", core_ranks=core_ranks,
            )
            if not best:
                raise ValueError(
                    f"PMS found no VMEM-feasible controller configuration for "
                    f"TTMc mode {mode} at core ranks {core_ranks} (spec budget "
                    f"{spec.vmem_bytes * spec.vmem_usable_frac:.0f} bytes)"
                )
            return best[0].cfg

        cfg = _searched_cfg(auto_tune, "ttmc", st, mode, core_ranks, spec, _search)
    cfg = cfg or MemoryControllerConfig()
    n_in = st.nmodes - 1
    plan = plan_blocks(
        st,
        mode,
        tile_i=cfg.cache.tile_i,
        blk=cfg.dma.blk,
        in_tiles=cfg.cache.input_tiles(n_in),
    )
    in_ranks = tuple(core_ranks[m] for m in plan.in_modes)
    return PlannedTTMC(plan=plan, in_ranks=in_ranks, cfg=cfg)


def _tt_bond_pairs(tt_ranks: Sequence[int], nmodes: int) -> tuple[tuple[int, int], ...]:
    """Per-core (rl_k, rr_k) bond pairs from the N-1 interior TT ranks
    (boundary bonds are 1 by definition)."""
    tt_ranks = tuple(int(r) for r in tt_ranks)
    if len(tt_ranks) != nmodes - 1:
        raise ValueError(
            f"tt_ranks has {len(tt_ranks)} entries for a {nmodes}-mode "
            f"tensor (pass the N-1 interior TT ranks)"
        )
    bounds = (1,) + tt_ranks + (1,)
    return tuple((bounds[k], bounds[k + 1]) for k in range(nmodes))


@dataclasses.dataclass
class PlannedTTCore:
    """A compiled memory-controller instance of the TT-core-update kernel for
    one (tensor, output mode): the same device-resident BlockPlan layout as
    MTTKRP/TTMc, driving the Kronecker-of-two-interfaces Pallas kernel
    (repro.tt TT-ALS's per-mode contraction).  `in_rank_pairs` are the input
    cores' (rl, rr) bond pairs in plan.in_modes order (ascending, so the
    first `plan.mode` of them chain from the left); the output has
    rl_m * rr_m true columns."""

    plan: BlockPlan
    in_rank_pairs: tuple[tuple[int, int], ...]
    cfg: MemoryControllerConfig = dataclasses.field(
        default_factory=MemoryControllerConfig
    )
    layout: tuple = dataclasses.field(default=(), init=False, repr=False)

    def __post_init__(self):
        self.in_rank_pairs = tuple(
            (int(a), int(b)) for a, b in self.in_rank_pairs
        )
        self.layout = _plan_device_arrays(self.plan)

    @property
    def n_left(self) -> int:
        """Inputs left of the output mode: plan.in_modes is ascending, so
        exactly `plan.mode` of them precede it."""
        return self.plan.mode

    @property
    def out_pair(self) -> tuple[int, int]:
        return tt_out_pair(self.in_rank_pairs, self.n_left)

    @property
    def out_cols(self) -> int:
        return tt_out_cols(self.in_rank_pairs, self.n_left)

    def __call__(self, *in_mats: jax.Array) -> jax.Array:
        """Core interface matrices W_k = transpose(G_k,(1,0,2)).reshape(I_k,
        rl_k*rr_k) for the N-1 *input* modes (plan.in_modes order), true
        shapes.  Returns (out_rows_unpadded, rl_m*rr_m)."""
        p = self.plan
        assert len(in_mats) == p.n_in
        pads = tuple(
            pad_factor(f, rows, rank_padded(a * b))
            for f, rows, (a, b) in zip(in_mats, p.in_rows, self.in_rank_pairs)
        )
        out = self.call_padded(pads)
        return out[: p.out_rows, : self.out_cols]

    def call_padded(self, in_mats_pad: Sequence[jax.Array], layout=None) -> jax.Array:
        """Run the kernel on already row/lane-padded interface matrices (the
        PlannedTT sweep path).  Returns the padded (out_rows, Pp) tile with
        unvisited output tiles zeroed.  `layout` as in
        `PlannedTTMC.call_padded`."""
        p = self.plan
        layout = self.layout if layout is None else layout
        return ttcore_pallas_call(
            *layout,
            tuple(in_mats_pad),
            tile_i=p.tile_i,
            in_tiles=p.in_tiles,
            in_rank_pairs=self.in_rank_pairs,
            n_left=self.n_left,
            out_rows=p.out_rows,
        )

    def output(self, mats: Sequence[jax.Array], true_rows: int) -> jax.Array:
        return self(*(mats[m] for m in self.plan.in_modes))[:true_rows]


def make_planned_ttcore(
    st: SparseTensor,
    mode: int,
    tt_ranks: Sequence[int],
    *,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> PlannedTTCore:
    """Build the memory layout + TT-core kernel instance for one output mode.

    Args:
      st: host-side COO tensor (>= 3 modes).
      mode: the output mode m — the kernel computes the TT-ALS right-hand
        side B_m (nnz-restricted Kronecker of the left/right interface
        chains).
      tt_ranks: the N-1 INTERIOR TT bond ranks (boundary bonds are 1); the
        instance's `in_rank_pairs` are the per-core (rl, rr) pairs in
        plan.in_modes order.  Each interface matrix is lane-padded to its
        own `rank_padded(rl_k*rr_k)`; the output carries rl_m*rr_m true
        columns, lane-padded to `rank_padded(rl_m*rr_m)`.
      cfg / auto_tune / spec: controller configuration, or let the PMS tune
        it for the TT kernel specifically (two interface scratch chains
        change the VMEM constraint and the roofline).

    Returns:
      A `PlannedTTCore` holding the device-resident BlockPlan layout — the
      SAME layout `make_planned_mttkrp` would build for this (tensor, mode,
      cfg); only the kernel differs."""
    pairs = _tt_bond_pairs(tt_ranks, st.nmodes)
    auto_tune, spec = _resolve_tune(auto_tune, spec)
    if auto_tune:
        def _search():
            best = pms_search(
                st, mode, max(max(p) for p in pairs), spec=spec, top_k=1,
                kernel="tt", core_ranks=tuple(int(r) for r in tt_ranks),
            )
            if not best:
                raise ValueError(
                    f"PMS found no VMEM-feasible controller configuration for "
                    f"TT mode {mode} at TT ranks {tuple(tt_ranks)} (spec budget "
                    f"{spec.vmem_bytes * spec.vmem_usable_frac:.0f} bytes)"
                )
            return best[0].cfg

        cfg = _searched_cfg(
            auto_tune, "tt", st, mode, tuple(int(r) for r in tt_ranks), spec, _search
        )
    cfg = cfg or MemoryControllerConfig()
    n_in = st.nmodes - 1
    plan = plan_blocks(
        st,
        mode,
        tile_i=cfg.cache.tile_i,
        blk=cfg.dma.blk,
        in_tiles=cfg.cache.input_tiles(n_in),
    )
    in_rank_pairs = tuple(pairs[m] for m in plan.in_modes)
    return PlannedTTCore(
        plan=plan, in_rank_pairs=in_rank_pairs, cfg=cfg
    )


@dataclasses.dataclass
class PlannedCPALS(PlannedWorkspace):
    """Per-mode plan cache driving the whole CP-ALS loop on the memory
    controller (paper Alg. 1 on the Alg. 5 layout).

    One `PlannedMTTKRP` per output mode — each holds its own remapped,
    device-resident copy of the non-zero stream — constructed once and reused
    for every ALS iteration, so the plan/remap cost is amortized over the
    decomposition exactly as the paper amortizes the FPGA layout generation
    over the (many-iteration) ALS run.

    The steady-state iteration is `sweep`: one jitted function running a full
    ALS iteration (every mode's MTTKRP -> gram -> solve -> normalize, plus the
    on-device fit).  Factor padding/residency and the host drive loop come
    from `PlannedWorkspace` — this class supplies only the CP sweep body.
    """

    ops: dict[int, PlannedMTTKRP]
    shape: tuple[int, ...]
    rank: int

    @property
    def lane_ranks(self) -> tuple[int, ...]:
        return (self.rank,) * self.nmodes

    @property
    def rank_pad(self) -> int:
        """CP's single lane padding (every mode shares rank R)."""
        return rank_padded(self.rank)

    def plan_for(self, mode: int) -> BlockPlan:
        return self.ops[mode].plan

    def _geoms(self) -> dict[int, BlockPlan]:
        return {m: op.plan for m, op in self.ops.items()}

    def _layout_bytes(self) -> int:
        return planned_layout_bytes(self.ops)

    def _build_sweep(self) -> Callable:
        shape, rank, nmodes = self.shape, self.rank, self.nmodes
        rp, prows = self.rank_pad, self.padded_rows
        ops = self.ops

        def sweep(layouts, facs, idx, val, norm_x_sq, first):
            facs = list(facs)
            lam = None
            for m in range(nmodes):
                p = ops[m].plan
                with sweep_scope("cp", "kernel", m):
                    in_facs = tuple(
                        facs[im][: p.in_rows[n]] for n, im in enumerate(p.in_modes)
                    )
                    out = mttkrp_pallas_call(
                        *layouts[m],
                        in_facs,
                        tile_i=p.tile_i,
                        in_tiles=p.in_tiles,
                        out_rows=p.out_rows,
                    )
                with sweep_scope("cp", "update", m):
                    mt = out[: shape[m], :rank]
                    true = [f[:s, :rank] for f, s in zip(facs, shape)]
                    true, lam = _update_mode(mt, true, m, first)
                    # Re-pad in place of the old padded factor (padding rows
                    # and lanes stay exactly zero, so grams/fit in padded
                    # space match the true-shape computation bit for bit).
                    f = true[m]
                    facs[m] = jnp.zeros((prows[m], rp), f.dtype).at[: shape[m], :rank].set(f)
            with sweep_scope("cp", "fit"):
                true = [f[:s, :rank] for f, s in zip(facs, shape)]
                fit = fit_value(idx, val, true, lam, norm_x_sq)
            return tuple(facs), lam, fit

        return jax.jit(sweep, static_argnames=("first",))

    def sweep(self, facs, idx, val, norm_x_sq, *, first: bool = False):
        """One jitted ALS iteration in padded space (the
        `PlannedWorkspace.sweep` contract).

        Args: `facs` — the rank-padded factor tuple; `idx`, `val` — the raw
        COO stream (any order — only the fit's inner product reads it; the
        per-mode remapped copies live inside the plans); `norm_x_sq` —
        ||X||_F^2 as a device scalar; `first` — first-ALS-iteration
        normalization convention (max(norm, 1)); static — one retrace when
        it flips to False.  Returns (new padded factors, lam, fit)."""
        return super().sweep(facs, idx, val, norm_x_sq, first=first)

    def _sweep_call(self, facs, *args, it: int):
        return self.sweep(facs, *args, first=(it == 0))

    def _sweep_variants(self) -> tuple[dict, ...]:
        return ({"first": True}, {"first": False})

    def mttkrp_fn(self, indices, values, factors, mode, out_rows):
        """The `cp_als(mttkrp_fn=...)` seam: the stream args are ignored —
        each mode's remapped copy already lives on device in its plan."""
        return self.ops[mode].output(factors, out_rows)

    def vmem_model_bytes(self) -> int:
        rp = self.rank_pad
        return max(
            op.cfg.vmem_bytes(rp, n_in=op.plan.n_in) for op in self.ops.values()
        )

    def pms_estimates(self, spec: TPUSpec = TPUSpec()) -> dict[int, Any]:
        """Exact per-mode PMS estimates from the built plans — the predicted
        side of `obs.calibrate`'s achieved_pct join (measured fills and
        padding, not the analytic occupancy model)."""
        return {
            m: predict_from_plan(op.plan, self.rank, op.cfg, spec)
            for m, op in self.ops.items()
        }

    def _build_fallback_sweep(self) -> Callable:
        """Reference degradation target of the "fallback" guard policy: the
        same ALS iteration as `_build_sweep` with the per-mode Pallas calls
        replaced by the pure-JAX Approach-1 MTTKRP on the raw stream (drive's
        args already carry it for the fit).  Operates on the SAME padded
        factors, so the switch reuses the last good iterate unchanged."""
        shape, rank, nmodes = self.shape, self.rank, self.nmodes
        rp, prows = self.rank_pad, self.padded_rows

        def sweep(facs, idx, val, norm_x_sq, first):
            facs = list(facs)
            lam = None
            for m in range(nmodes):
                true = [f[:s, :rank] for f, s in zip(facs, shape)]
                mt = mttkrp_jax(
                    idx, val, true, m, shape[m],
                    method="approach1", sorted_by_mode=False,
                )
                true, lam = _update_mode(mt, true, m, first)
                f = true[m]
                facs[m] = jnp.zeros((prows[m], rp), f.dtype).at[: shape[m], :rank].set(f)
            true = [f[:s, :rank] for f, s in zip(facs, shape)]
            fit = fit_value(idx, val, true, lam, norm_x_sq)
            return tuple(facs), lam, fit

        jitted = jax.jit(sweep, static_argnames=("first",))
        return lambda facs, *args, it: jitted(facs, *args, first=(it == 0))


def make_planned_cp_als(
    st: SparseTensor,
    rank: int,
    *,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> PlannedCPALS:
    """Build the full ALS workspace: one tuned plan per output mode.

    Args:
      st: host-side COO tensor (>= 3 modes).  The Tensor Remapper runs once
        per mode here — this call is the whole layout-generation cost the
        paper amortizes over the ALS run.
      rank: CP rank R.  Kernels compute at `rank_padded(R)` lanes (>= 128,
        128-multiple); results are sliced back to R.
      cfg: controller configuration shared by every mode (default config if
        None).  Ignored when auto_tune=True.
      auto_tune: run the PMS per output mode (modes have different shapes /
        locality, Sec. 5.3) and take each mode's best configuration.
      spec: target-hardware constants for the PMS search.

    Returns:
      A `PlannedCPALS` whose per-mode remapped layouts are device-resident
      for the workspace's lifetime (`plan_bytes()` reports the HBM spend —
      the per-mode-copies trade).  Reuse it across `cp_als(planned=ws)`
      calls to skip the remap entirely."""
    ops = {
        m: make_planned_mttkrp(
            st, m, rank, cfg=cfg, auto_tune=auto_tune, spec=spec
        )
        for m in range(st.nmodes)
    }
    return PlannedCPALS(ops=ops, shape=st.shape, rank=rank)


# ---------------------------------------------------------------------------
# Keyed plan cache for the one-shot dispatchers (mttkrp_auto / tucker_auto)
# ---------------------------------------------------------------------------

_PLAN_CACHE: OrderedDict[tuple, "PlannedMTTKRP | PlannedTTMC"] = OrderedDict()
# LRU bound: each entry pins a device-resident layout, so an unbounded cache
# lets a tenant churning tensor fingerprints grow resident HBM without limit.
# Env-overridable at import (REPRO_PLAN_CACHE_MAX) and at runtime
# (plan_cache_config).
_PLAN_CACHE_CAP = max(1, int(os.environ.get("REPRO_PLAN_CACHE_MAX", "32")))
_PLAN_CACHE_KINDS = ("mttkrp", "ttmc", "tt")
_PLAN_CACHE_STATS = {k: {"hits": 0, "misses": 0} for k in _PLAN_CACHE_KINDS}
_PLAN_CACHE_EVICTIONS = {"count": 0}


def plan_cache_config(maxsize: int | None = None) -> int:
    """Get (and optionally set) the plan cache's LRU bound.

    With `maxsize=None` returns the current bound.  With an integer, sets the
    bound (>= 1), immediately evicting least-recently-used entries down to it
    (counted in `plan_cache_stats()["evictions"]`), and returns the new
    bound.  The initial bound comes from `REPRO_PLAN_CACHE_MAX` (default
    32)."""
    global _PLAN_CACHE_CAP
    if maxsize is not None:
        if maxsize < 1:
            raise ValueError(f"plan cache maxsize must be >= 1, got {maxsize}")
        _PLAN_CACHE_CAP = int(maxsize)
        _evict_to_cap()
    return _PLAN_CACHE_CAP


def _evict_to_cap() -> None:
    while len(_PLAN_CACHE) > _PLAN_CACHE_CAP:
        key, _ = _PLAN_CACHE.popitem(last=False)
        _PLAN_CACHE_EVICTIONS["count"] += 1
        _metrics.counter("plan_cache.evictions").inc()
        _trace.event("plan_cache_evict", kind=str(key[0]), mode=int(key[2]))


def plan_cache_stats() -> dict:
    """Hit/miss/eviction counters of the shared plan cache.

    Returns:
      ``{"hits": int, "misses": int, "evictions": int, "size": int,
      "maxsize": int, "by_kind": {"mttkrp": {...}, "ttmc": {...},
      "tt": {...}}}`` — totals at the top level plus per-kernel-kind
      hit/miss counters.  A hit means a dispatcher call skipped the whole
      remap/layout build (bench_e2e reports first-vs-cached call times); an
      eviction means the LRU bound (`plan_cache_config`) dropped a resident
      layout.

    Invariants: the kinds are tracked separately precisely because the
    cache key carries a kind discriminator — no cross-kind collisions by
    construction; per-shard BlockPlans of the distributed path count under
    their kernel's kind (their keys additionally carry a shard field).
    Counters reset on `plan_cache_clear()`."""
    by_kind = {k: dict(v) for k, v in _PLAN_CACHE_STATS.items()}
    return {
        "hits": sum(v["hits"] for v in by_kind.values()),
        "misses": sum(v["misses"] for v in by_kind.values()),
        "evictions": _PLAN_CACHE_EVICTIONS["count"],
        "size": len(_PLAN_CACHE),
        "maxsize": _PLAN_CACHE_CAP,
        "by_kind": by_kind,
    }


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    for v in _PLAN_CACHE_STATS.values():
        v["hits"] = 0
        v["misses"] = 0
    _PLAN_CACHE_EVICTIONS["count"] = 0


def _planned_cached(
    kind: str,
    st: SparseTensor,
    mode: int,
    rank_key,
    cfg: MemoryControllerConfig | None,
    build: Callable,
    *,
    shard: tuple | None = None,
):
    """LRU-cached plan lookup keyed by (kernel kind, tensor content
    fingerprint, mode, rank key, controller config, shard) —
    repeated test/benchmark calls stop repaying the Tensor Remapper on every
    invocation.  The leading `kind` field keeps MTTKRP and TTMc plans for
    the same tensor/mode/rank from silently aliasing each other: the cached
    kernel instances carry kernel-specific state.  `shard` entries (a
    `(shard_index, nshards)` pair, None for the single-device dispatchers)
    are different: they cache raw, kernel-agnostic `BlockPlan`s, so their
    keys use a shared "layout" kind — CP and Tucker sharded workspaces for
    the same (tensor, cfg) reuse each other's shard layouts instead of
    repaying the remap — while hit/miss STATS stay attributed to the
    calling kernel's kind."""
    key = (
        "layout" if shard is not None else kind,
        st.fingerprint(),
        mode,
        rank_key,
        cfg or MemoryControllerConfig(),
        shard,
    )
    stats = _PLAN_CACHE_STATS[kind]
    t0 = time.perf_counter()
    op = _PLAN_CACHE.get(key)
    if op is not None:
        stats["hits"] += 1
        _PLAN_CACHE.move_to_end(key)
        if plans_validated():
            # REPRO_VALIDATE_PLANS: re-validate cached layouts on every hit —
            # a corrupted resident plan must not outlive detection just
            # because it skipped the build path.  Shard entries cache raw
            # BlockPlans; kind entries cache kernel ops carrying `.plan`.
            validate_plan(op if isinstance(op, BlockPlan) else op.plan)
        _metrics.counter("plan_cache.hits", kind=kind).inc()
        _metrics.histogram("plan_cache.hit_seconds", kind=kind).observe(
            time.perf_counter() - t0
        )
        _trace.event("plan_cache_hit", kind=kind, mode=mode)
        return op
    stats["misses"] += 1
    with _trace.span("plan_cache_build", kind=kind, mode=mode):
        op = build()
    _PLAN_CACHE[key] = op
    _evict_to_cap()
    _metrics.counter("plan_cache.misses", kind=kind).inc()
    _metrics.histogram("plan_cache.miss_build_seconds", kind=kind).observe(
        time.perf_counter() - t0
    )
    return op


def mttkrp_auto(
    st: SparseTensor,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    method: str = "pallas",
    cfg: MemoryControllerConfig | None = None,
    sorted_by_mode: bool | None = None,
) -> jax.Array:
    """One-shot dispatcher used by tests/benchmarks: 'pallas' | 'approach1' |
    'approach2'.  The pallas path caches its BlockPlan keyed on the tensor's
    content fingerprint (see `plan_cache_stats`).

    `sorted_by_mode` defaults to what the stream actually satisfies
    (`st.is_sorted_by(mode)`): `indices_are_sorted` is a correctness promise
    to XLA, not a hint, so it is never asserted for an unsorted stream."""
    rank = int(factors[0].shape[1])
    if method == "pallas":
        op = _planned_cached(
            "mttkrp", st, mode, rank, cfg,
            lambda: make_planned_mttkrp(st, mode, rank, cfg=cfg),
        )
        return op.output(factors, st.shape[mode])
    if sorted_by_mode is None:
        sorted_by_mode = st.is_sorted_by(mode)
    idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
    return mttkrp_jax(
        idx, val, factors, mode, st.shape[mode],
        method=method, sorted_by_mode=sorted_by_mode,
    )


def tucker_auto(
    st: SparseTensor,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    method: str = "pallas",
    cfg: MemoryControllerConfig | None = None,
) -> jax.Array:
    """One-shot sparse TTM-chain dispatcher (the Tucker-side analogue of
    `mttkrp_auto`): contract every factor but `mode` into X.

    Args:
      st: host-side COO tensor.
      factors: ALL N factor matrices, true shapes (I_m, R_m); the mode-th is
        not contracted (and its rank is not part of the cache key).  Input
        ranks are read off the factor shapes.
      mode: output mode of the unfolding.
      method: 'pallas' — the planned memory-controller kernel, its BlockPlan
        cached in the shared kind-keyed LRU (see
        `plan_cache_stats()["by_kind"]["ttmc"]`); 'reference' — the pure-jnp
        gather/Kronecker/segment_sum oracle.
      cfg: pallas-path controller configuration (part of the cache key).

    Returns:
      The unfolding Y_(mode), shape (I_mode, prod of input ranks), float32,
      column order row-major over ascending input mode.  Rank-padding
      invariant: the kernel pads each input factor to `rank_padded(R_m)`
      lanes internally and slices the true Kronecker width back out —
      callers never see padded shapes."""
    core_ranks = tuple(int(f.shape[1]) for f in factors)
    if method == "pallas":
        in_ranks = tuple(r for m, r in enumerate(core_ranks) if m != mode)
        op = _planned_cached(
            "ttmc", st, mode, in_ranks, cfg,
            lambda: make_planned_ttmc(st, mode, core_ranks, cfg=cfg),
        )
        return op.output(factors, st.shape[mode])
    if method != "reference":
        raise ValueError(f"unknown method {method!r}: expected 'pallas' or 'reference'")
    return ttmc_ref(
        jnp.asarray(st.indices), jnp.asarray(st.values), factors, mode, st.shape[mode]
    )


def tt_auto(
    st: SparseTensor,
    cores: Sequence[jax.Array],
    mode: int,
    *,
    method: str = "pallas",
    cfg: MemoryControllerConfig | None = None,
) -> jax.Array:
    """One-shot sparse TT-core dispatcher (the tensor-train analogue of
    `mttkrp_auto` / `tucker_auto`): the TT-ALS right-hand side B_mode from
    the left/right interface chains of the other cores.

    Args:
      st: host-side COO tensor.
      cores: ALL N TT cores, shapes (rl_k, I_k, rr_k) with boundary bonds 1;
        the mode-th is not contracted (its bonds still set the output
        width).  Bond ranks are read off the core shapes.
      mode: output mode of the update.
      method: 'pallas' — the planned memory-controller kernel, its BlockPlan
        cached in the shared kind-keyed LRU (see
        `plan_cache_stats()["by_kind"]["tt"]`); 'reference' — the pure-jnp
        gather/chain/segment_sum oracle.
      cfg: pallas-path controller configuration (part of the cache key).

    Returns:
      B_mode, shape (I_mode, rl_mode * rr_mode), float32, columns row-major
      over (rl, rr).  Rank-padding invariant: the kernel pads each interface
      matrix to `rank_padded(rl_k*rr_k)` lanes internally and slices the
      true width back out — callers never see padded shapes."""
    pairs = tuple((int(c.shape[0]), int(c.shape[2])) for c in cores)
    if method == "pallas":
        in_pairs = tuple(p for m, p in enumerate(pairs) if m != mode)
        tt_ranks = tuple(pairs[k][1] for k in range(len(cores) - 1))
        op = _planned_cached(
            "tt", st, mode, in_pairs, cfg,
            lambda: make_planned_ttcore(st, mode, tt_ranks, cfg=cfg),
        )
        mats = [jnp.transpose(c, (1, 0, 2)).reshape(c.shape[1], -1) for c in cores]
        return op.output(mats, st.shape[mode])
    if method != "reference":
        raise ValueError(f"unknown method {method!r}: expected 'pallas' or 'reference'")
    return ttcore_ref(
        jnp.asarray(st.indices), jnp.asarray(st.values), cores, mode, st.shape[mode]
    )


# ---------------------------------------------------------------------------
# Sharded planned decomposition (the repro.dist.planned substrate)
# ---------------------------------------------------------------------------
#
# The distributed composition of the whole repo: the COO stream is partitioned
# into balanced output-mode tile ranges (dist/sharding.partition_stream — the
# paper's "each DMA engine serves one slice of the remapped stream" posture),
# one BlockPlan is built per (shard, mode) so every shard's remapped layout is
# local to its device, and the existing Pallas kernels run unchanged under
# shard_map with ONE psum of partial factor rows per mode.  Because shard
# boundaries are tile_i-aligned, each device's kernel writes a disjoint set of
# output tiles and the psum is a pure reassembly (plus float reassociation).


@dataclasses.dataclass
class _ShardStack:
    """Stacked (shard-major) BlockPlan layouts for one output mode: shard d's
    layout occupies row d of every array, padded to the widest shard's block
    count.  Padding blocks carry zero values and *repeat the last real
    block's tile ids*, so they re-zero no accumulator, trigger no extra tile
    fills, and contribute exactly nothing.  Geometry fields mirror BlockPlan
    (identical across shards: same controller config, same global shape)."""

    block_it: jax.Array  # (D, NB) int32 — global output tile ids
    block_in: tuple  # n_in x (D, NB) int32
    vals: jax.Array  # (D, NB, 1, blk) f32
    iloc: jax.Array  # (D, NB, 1, blk) int32
    in_locs: tuple  # n_in x (D, NB, 1, blk) int32
    tile_i: int
    in_tiles: tuple[int, ...]
    blk: int
    out_rows: int  # padded global I_out (multiple of tile_i)
    in_rows: tuple[int, ...]
    mode: int
    in_modes: tuple[int, ...]
    shard_nblocks: tuple[int, ...]  # true per-shard block counts (pre-pad)
    shard_nnz: tuple[int, ...]
    tile_bounds: tuple[int, ...]  # partition cut points, in tile_i units

    @property
    def nshards(self) -> int:
        return int(self.block_it.shape[0])

    @property
    def nblocks(self) -> int:
        """Padded per-shard block count (the stack width)."""
        return int(self.block_it.shape[1])

    @property
    def n_in(self) -> int:
        return len(self.in_modes)

    def tree(self) -> dict:
        """The pytree handed through shard_map (leading dim = shard axis)."""
        return {
            "block_it": self.block_it,
            "block_in": self.block_in,
            "vals": self.vals,
            "iloc": self.iloc,
            "in_locs": self.in_locs,
        }

    def tree_specs(self, axes) -> dict:
        """PartitionSpecs matching `tree()`: leading dim over the data axes."""
        row, cube = P(axes, None), P(axes, None, None, None)
        return {
            "block_it": row,
            "block_in": tuple(row for _ in self.block_in),
            "vals": cube,
            "iloc": cube,
            "in_locs": tuple(cube for _ in self.in_locs),
        }


def _empty_shard_plan(shape: tuple[int, ...], mode: int, cfg: MemoryControllerConfig) -> BlockPlan:
    """An all-padding layout for a shard that owns no non-zeros (possible
    when nnz or the output tile count is smaller than the shard count): one
    zero-value block targeting tile 0, which accumulates exactly zero."""
    nmodes = len(shape)
    in_modes = tuple(m for m in range(nmodes) if m != mode)
    n_in = len(in_modes)
    in_tiles = cfg.cache.input_tiles(n_in)
    blk, tile_i = cfg.dma.blk, cfg.cache.tile_i
    ceil_to = lambda x, t: ((x + t - 1) // t) * t
    return BlockPlan(
        vals=np.zeros((blk,), np.float32),
        iloc=np.zeros((blk,), np.int32),
        in_locs=tuple(np.zeros((blk,), np.int32) for _ in range(n_in)),
        block_it=np.zeros((1,), np.int32),
        block_in=tuple(np.zeros((1,), np.int32) for _ in range(n_in)),
        tile_i=tile_i,
        in_tiles=in_tiles,
        blk=blk,
        out_rows=ceil_to(shape[mode], tile_i),
        in_rows=tuple(ceil_to(shape[m], t) for m, t in zip(in_modes, in_tiles)),
        mode=mode,
        in_modes=in_modes,
        nnz=0,
    )


def _stack_shard_plans(plans: Sequence[BlockPlan], part, dist) -> _ShardStack:
    """Pad per-shard BlockPlans to a common block count and stack them
    shard-major, then device_put every array with its NamedSharding so each
    shard's layout is resident on its own device (never gathered)."""
    p0 = plans[0]
    for p in plans[1:]:
        assert (
            p.tile_i, p.in_tiles, p.blk, p.out_rows, p.in_rows, p.in_modes
        ) == (
            p0.tile_i, p0.in_tiles, p0.blk, p0.out_rows, p0.in_rows, p0.in_modes
        ), "shard plans must share controller geometry"
    nd = len(plans)
    nb = max(p.nblocks for p in plans)
    n_in, blk = p0.n_in, p0.blk
    block_it = np.zeros((nd, nb), np.int32)
    block_in = [np.zeros((nd, nb), np.int32) for _ in range(n_in)]
    vals = np.zeros((nd, nb, 1, blk), np.float32)
    iloc = np.zeros((nd, nb, 1, blk), np.int32)
    in_locs = [np.zeros((nd, nb, 1, blk), np.int32) for _ in range(n_in)]
    for d, p in enumerate(plans):
        k = p.nblocks
        block_it[d, :k] = p.block_it
        block_it[d, k:] = p.block_it[-1]
        for n in range(n_in):
            block_in[n][d, :k] = p.block_in[n]
            block_in[n][d, k:] = p.block_in[n][-1]
        vals[d, :k] = p.vals.reshape(k, 1, blk)
        iloc[d, :k] = p.iloc.reshape(k, 1, blk)
        for n in range(n_in):
            in_locs[n][d, :k] = p.in_locs[n].reshape(k, 1, blk)
    mesh, axes = dist.mesh, dist.data_axes()
    sh_row = NamedSharding(mesh, P(axes, None))
    sh_cube = NamedSharding(mesh, P(axes, None, None, None))
    return _ShardStack(
        block_it=jax.device_put(block_it, sh_row),
        block_in=tuple(jax.device_put(b, sh_row) for b in block_in),
        vals=jax.device_put(vals, sh_cube),
        iloc=jax.device_put(iloc, sh_cube),
        in_locs=tuple(jax.device_put(l, sh_cube) for l in in_locs),
        tile_i=p0.tile_i,
        in_tiles=p0.in_tiles,
        blk=blk,
        out_rows=p0.out_rows,
        in_rows=p0.in_rows,
        mode=p0.mode,
        in_modes=p0.in_modes,
        shard_nblocks=tuple(p.nblocks for p in plans),
        shard_nnz=tuple(p.nnz for p in plans),
        tile_bounds=part.tile_bounds,
    )


def _sharded_mode_stack(
    st: SparseTensor,
    mode: int,
    cfg: MemoryControllerConfig,
    dist,
    kind: str,
):
    """Partition the stream for one output mode and build its shard-stacked
    layout.  Per-shard BlockPlans go through the shared LRU with shard-aware
    keys (`_planned_cached(shard=(d, nshards))`), so rebuilding a workspace
    for the same tensor skips the per-shard Tensor Remapper.  The cached
    objects are raw BlockPlans, which depend only on (stream, mode, cfg) —
    the rank key is a constant sentinel, so rebuilding the same tensor at a
    different rank still hits.  Returns (partition, stack)."""
    from ..dist.sharding import partition_stream

    nshards = dist.dp_size()
    with _trace.span("shard_stack", kind=kind, mode=mode, nshards=nshards):
        part = partition_stream(st, mode, nshards, tile=cfg.cache.tile_i)
        n_in = st.nmodes - 1
        plans = []
        for d, shard in enumerate(part.shards):
            if shard.nnz == 0:
                plans.append(_empty_shard_plan(st.shape, mode, cfg))
                continue
            plans.append(
                _planned_cached(
                    kind, shard, mode, "layout", cfg,
                    lambda shard=shard: plan_blocks(
                        shard,
                        mode,
                        tile_i=cfg.cache.tile_i,
                        blk=cfg.dma.blk,
                        in_tiles=cfg.cache.input_tiles(n_in),
                    ),
                    shard=(d, nshards),
                )
            )
        stack = _stack_shard_plans(plans, part, dist)
    # The stacked sweep runs every shard for the widest shard's block count,
    # so max/mean block imbalance is the direct makespan-inflation factor.
    nblocks = [max(1, p.nblocks) for p in plans]
    _metrics.histogram("sharded.block_imbalance", kind=kind).observe(
        max(nblocks) * len(nblocks) / sum(nblocks)
    )
    return part, stack


def _stack_fit_stream(part, shape: tuple[int, ...], dist):
    """Shard-stacked raw COO stream for on-device fit terms: each shard's
    slice zero-padded to the widest shard (padding values are 0, so partial
    inner products are unchanged).  Returns (idx, val) with leading shard
    dim, device_put with their NamedShardings."""
    nd = part.nshards
    nnz_max = max(1, max(part.shard_nnz))
    idx = np.zeros((nd, nnz_max, len(shape)), np.int32)
    val = np.zeros((nd, nnz_max), np.float32)
    for d, sh in enumerate(part.shards):
        idx[d, : sh.nnz] = sh.indices
        val[d, : sh.nnz] = sh.values
    mesh, axes = dist.mesh, dist.data_axes()
    return (
        jax.device_put(idx, NamedSharding(mesh, P(axes, None, None))),
        jax.device_put(val, NamedSharding(mesh, P(axes, None))),
    )


def _stack_args(arrs: dict) -> tuple:
    """One shard's row of a stack as the kernels' leading arguments (inside
    shard_map every stacked array arrives with a leading local dim of 1).
    The kernels zero every output tile their blocks do not visit, so the
    psum over shards is a pure reassembly of disjoint contributions."""
    return (
        arrs["block_it"][0],
        tuple(t[0] for t in arrs["block_in"]),
        arrs["vals"][0],
        arrs["iloc"][0],
        tuple(l[0] for l in arrs["in_locs"]),
    )


def _stack_mttkrp_call(stack: _ShardStack, arrs: dict, in_facs) -> jax.Array:
    return mttkrp_pallas_call(
        *_stack_args(arrs), in_facs,
        tile_i=stack.tile_i, in_tiles=stack.in_tiles, out_rows=stack.out_rows,
    )


def _stack_ttmc_call(
    stack: _ShardStack, arrs: dict, in_facs, in_ranks: tuple[int, ...]
) -> jax.Array:
    return ttmc_pallas_call(
        *_stack_args(arrs), in_facs,
        tile_i=stack.tile_i, in_tiles=stack.in_tiles, in_ranks=in_ranks,
        out_rows=stack.out_rows,
    )


def _stack_ttcore_call(
    stack: _ShardStack,
    arrs: dict,
    in_mats,
    in_rank_pairs: tuple[tuple[int, int], ...],
    n_left: int,
) -> jax.Array:
    return ttcore_pallas_call(
        *_stack_args(arrs), in_mats,
        tile_i=stack.tile_i, in_tiles=stack.in_tiles,
        in_rank_pairs=in_rank_pairs, n_left=n_left, out_rows=stack.out_rows,
    )


def _tuned_cfg(
    st: SparseTensor,
    mode: int,
    rank: int,
    nshards: int,
    cfg: MemoryControllerConfig | None,
    auto_tune: bool | str,
    spec: TPUSpec | str,
    kernel: str = "mttkrp",
    core_ranks: Sequence[int] | None = None,
) -> MemoryControllerConfig:
    """Resolve one mode's controller configuration for the sharded path:
    the sharded PMS's worst-shard-makespan winner when auto_tune is set
    (persisted/reused on disk for auto_tune="cached", keyed with the shard
    count — a 2-shard winner is not a 4-shard winner), else the explicit
    cfg, else the default."""
    auto_tune, spec = _resolve_tune(auto_tune, spec)
    if auto_tune:
        def _search():
            from ..core.pms import search_sharded

            best = search_sharded(
                st, mode, rank, nshards, spec=spec, top_k=1,
                kernel=kernel, core_ranks=core_ranks,
            )
            if not best:
                raise ValueError(
                    f"sharded PMS found no VMEM-feasible {kernel} configuration "
                    f"for mode {mode} over {nshards} shards (spec budget "
                    f"{spec.vmem_bytes * spec.vmem_usable_frac:.0f} bytes)"
                )
            return best[0].cfg

        rank_key = rank if core_ranks is None else tuple(int(r) for r in core_ranks)
        return _searched_cfg(
            auto_tune, kernel, st, mode, rank_key, spec, _search, nshards=nshards
        )
    return cfg or MemoryControllerConfig()


def _resolve_dist(dist, devices: int | None):
    """Default ShardingPlan for the sharded planned path: an explicit plan
    wins; otherwise a 1-D `shard` mesh over the first `devices` (or all)
    local devices (dist/planned.shard_plan)."""
    if dist is None:
        from ..dist.planned import shard_plan

        dist = shard_plan(devices)
    elif devices is not None and dist.dp_size() != devices:
        raise ValueError(
            f"both dist (dp_size={dist.dp_size()}) and devices={devices} "
            f"were passed and they disagree"
        )
    if dist.mesh is None or not dist.data_axes():
        raise ValueError(
            "the sharded planned path needs a ShardingPlan with a mesh and "
            "at least one data axis (see repro.dist.planned.shard_plan)"
        )
    return dist


@dataclasses.dataclass
class ShardedPlannedMTTKRP:
    """One (tensor, mode) MTTKRP distributed over a ShardingPlan's data axes.

    The stream is partitioned into balanced, tile_i-aligned output ranges;
    each shard's remapped BlockPlan layout lives on its own device
    (`_ShardStack` row) and a call runs the unchanged Pallas kernel under
    shard_map, psum-reducing the partial factor rows — `mttkrp_sharded`'s
    Table-1 `I_out*R` collective, now fed by the planned kernel instead of
    the pure-JAX approaches."""

    stack: _ShardStack
    dist: Any  # ShardingPlan with mesh + data axes
    rank: int
    cfg: MemoryControllerConfig = dataclasses.field(
        default_factory=MemoryControllerConfig
    )
    _call_fn: Callable | None = dataclasses.field(default=None, repr=False)

    def _build_call(self) -> Callable:
        stack = self.stack
        mesh, axes = self.dist.mesh, self.dist.data_axes()
        fac_specs = tuple(P(None, None) for _ in range(stack.n_in))

        def local_fn(arrs, pads):
            out = _stack_mttkrp_call(stack, arrs, pads)
            return jax.lax.psum(out, axes)

        def call(arrs, pads):
            return jax.shard_map(
                local_fn,
                mesh=mesh,
                in_specs=(stack.tree_specs(axes), fac_specs),
                out_specs=P(None, None),
                check_vma=False,
            )(arrs, pads)

        return jax.jit(call)

    def __call__(self, *in_factors: jax.Array) -> jax.Array:
        """Factors for the N-1 *input* modes (stack.in_modes order), true
        shapes.  Returns (out_rows_padded, rank) sliced to true columns."""
        s = self.stack
        assert len(in_factors) == s.n_in
        rp = rank_padded(self.rank)
        pads = tuple(
            pad_factor(f, rows, rp) for f, rows in zip(in_factors, s.in_rows)
        )
        if self._call_fn is None:
            self._call_fn = self._build_call()
        out = self._call_fn(s.tree(), pads)
        return out[: s.out_rows, : self.rank]

    def output(self, factors: Sequence[jax.Array], true_rows: int) -> jax.Array:
        return self(*(factors[m] for m in self.stack.in_modes))[:true_rows]


def make_sharded_planned_mttkrp(
    st: SparseTensor,
    mode: int,
    rank: int,
    *,
    dist=None,
    devices: int | None = None,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> ShardedPlannedMTTKRP:
    """Build the distributed memory layout + kernel instance for one output
    mode.  With auto_tune=True the PMS scores configurations by their *worst
    shard* (`pms.search_sharded` makespan) before the layouts are built."""
    dist = _resolve_dist(dist, devices)
    cfg = _tuned_cfg(st, mode, rank, dist.dp_size(), cfg, auto_tune, spec)
    _, stack = _sharded_mode_stack(st, mode, cfg, dist, "mttkrp")
    return ShardedPlannedMTTKRP(
        stack=stack, dist=dist, rank=rank, cfg=cfg
    )


@dataclasses.dataclass
class ShardedPlannedCPALS(ShardedWorkspace):
    """Distributed `PlannedCPALS`: the whole CP-ALS loop on shard-local
    memory-controller layouts.

    One `_ShardStack` per output mode — shard d of mode m's stack holds the
    remapped, device-resident layout of shard d's slice of the stream,
    partitioned by mode-m output tiles (`partition_stream`).  `sweep` runs a
    full ALS iteration as ONE jitted shard_map: per mode, every device runs
    the Pallas kernel on its local layout and a single `psum` reassembles the
    factor rows (shards own disjoint tile ranges, so the sum merges rather
    than accumulates); gram/solve/normalize then run replicated.  The fit is
    computed from psum'd scalars — each shard contributes the inner product
    over its own stream slice.  Padding/residency and the drive loop come
    from `ShardedWorkspace` — this class supplies only the CP sweep body."""

    stacks: dict[int, _ShardStack]
    dist: Any  # ShardingPlan with mesh + data axes
    shape: tuple[int, ...]
    rank: int
    cfgs: dict[int, MemoryControllerConfig]
    idx_sh: jax.Array  # (D, max shard nnz, N) fit stream, zero-padded
    val_sh: jax.Array  # (D, max shard nnz)

    @property
    def lane_ranks(self) -> tuple[int, ...]:
        return (self.rank,) * self.nmodes

    @property
    def rank_pad(self) -> int:
        """CP's single lane padding (every mode shares rank R)."""
        return rank_padded(self.rank)

    def _stream_args(self) -> tuple:
        return (self.idx_sh, self.val_sh)

    def _build_sweep(self) -> Callable:
        shape, rank, nmodes = self.shape, self.rank, self.nmodes
        rp, prows = self.rank_pad, self.padded_rows
        stacks = self.stacks
        mesh, axes = self.dist.mesh, self.dist.data_axes()
        arr_specs = {m: stacks[m].tree_specs(axes) for m in range(nmodes)}
        fac_specs = tuple(P(None, None) for _ in range(nmodes))

        def local_sweep(arrs, idx, val, facs, norm_x_sq, first):
            facs = list(facs)
            lam = None
            for m in range(nmodes):
                s = stacks[m]
                in_facs = tuple(
                    facs[im][: s.in_rows[n]] for n, im in enumerate(s.in_modes)
                )
                out = _stack_mttkrp_call(s, arrs[m], in_facs)
                # The single collective per mode: partial factor rows from
                # disjoint tile ranges -> the full MTTKRP output.
                mt = jax.lax.psum(out, axes)[: shape[m], :rank]
                true = [f[:sz, :rank] for f, sz in zip(facs, shape)]
                true, lam = _update_mode(mt, true, m, first)
                f = true[m]
                facs[m] = (
                    jnp.zeros((prows[m], rp), f.dtype).at[: shape[m], :rank].set(f)
                )
            true = [f[:sz, :rank] for f, sz in zip(facs, shape)]
            # Fit from psum'd scalars: each shard's slice of <X, model>
            # (padding entries carry value 0), reduced once.
            inner = jax.lax.psum(inner_with_model(idx[0], val[0], true, lam), axes)
            resid_sq = jnp.maximum(
                norm_x_sq + model_norm_sq(true, lam) - 2.0 * inner, 0.0
            )
            fit = 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)
            return tuple(facs), lam, fit

        def sweep(arrs, idx_sh, val_sh, facs, norm_x_sq, first):
            fn = functools.partial(local_sweep, first=first)
            return jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(
                    arr_specs,
                    P(axes, None, None),
                    P(axes, None),
                    fac_specs,
                    P(),
                ),
                out_specs=(fac_specs, P(None), P()),
                check_vma=False,
            )(arrs, idx_sh, val_sh, facs, norm_x_sq)

        return jax.jit(sweep, static_argnames=("first",))

    def sweep(self, facs, norm_x_sq, *, first: bool = False):
        """One jitted distributed ALS iteration in padded space.

        Args: `facs` — the rank-padded factor tuple from `pad_factors`
        (replicated); `norm_x_sq` — ||X||^2 scalar.  Returns (new padded
        factors, lam, fit scalar on device) — the same contract as
        `PlannedCPALS.sweep` minus the stream arguments (each shard's slice
        already lives on its device)."""
        return super().sweep(facs, norm_x_sq, first=first)

    def _sweep_call(self, facs, *args, it: int):
        return self.sweep(facs, *args, first=(it == 0))


def make_sharded_planned_cp_als(
    st: SparseTensor,
    rank: int,
    *,
    dist=None,
    devices: int | None = None,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> ShardedPlannedCPALS:
    """Build the distributed ALS workspace: one partition + shard-stacked
    layout per output mode (each mode partitions by ITS OWN output
    coordinate, exactly as each mode gets its own remap in Alg. 5).

    dist/devices: a ShardingPlan with >= 1 data axis, or a device count for
    the default 1-D `shard` mesh (None = all local devices).  With
    auto_tune=True each mode's controller configuration is chosen by the
    sharded PMS (worst-shard makespan, `pms.search_sharded`)."""
    dist = _resolve_dist(dist, devices)
    nshards = dist.dp_size()
    stacks: dict[int, _ShardStack] = {}
    cfgs: dict[int, MemoryControllerConfig] = {}
    part0 = None
    for m in range(st.nmodes):
        mcfg = _tuned_cfg(st, m, rank, nshards, cfg, auto_tune, spec)
        cfgs[m] = mcfg
        part, stacks[m] = _sharded_mode_stack(st, m, mcfg, dist, "mttkrp")
        if m == 0:
            part0 = part
    idx_sh, val_sh = _stack_fit_stream(part0, st.shape, dist)
    return ShardedPlannedCPALS(
        stacks=stacks,
        dist=dist,
        shape=st.shape,
        rank=rank,
        cfgs=cfgs,
        idx_sh=idx_sh,
        val_sh=val_sh,
    )


@dataclasses.dataclass
class ShardedPlannedTucker(ShardedWorkspace):
    """Distributed `PlannedTucker`: the whole HOOI loop on shard-local
    memory-controller layouts — the TTM-chain mirror of
    `ShardedPlannedCPALS` (same partitions, same stacks, Kronecker-chain
    kernel, per-mode `rank_padded(R_m)` lane contracts).  The fit needs no
    stream at all: the core comes from the last mode's psum'd unfolding and
    ||X||^2 - ||G||^2 gives the residual (orthonormal factors)."""

    stacks: dict[int, _ShardStack]
    dist: Any
    shape: tuple[int, ...]
    core_ranks: tuple[int, ...]
    cfgs: dict[int, MemoryControllerConfig]

    @property
    def lane_ranks(self) -> tuple[int, ...]:
        return self.core_ranks

    def in_ranks(self, mode: int) -> tuple[int, ...]:
        return tuple(self.core_ranks[im] for im in self.stacks[mode].in_modes)

    def _build_sweep(self) -> Callable:
        # Lazy: repro.tucker imports this module at load time.
        from ..tucker.hooi import (
            _core_from_unfolding,
            _factor_from_unfolding,
            core_fit_value,
        )

        shape, core_ranks, nmodes = self.shape, self.core_ranks, self.nmodes
        rps, prows = self.rank_pads, self.padded_rows
        stacks = self.stacks
        mesh, axes = self.dist.mesh, self.dist.data_axes()
        in_ranks = {m: self.in_ranks(m) for m in range(nmodes)}
        out_cols = {m: kron_cols(in_ranks[m]) for m in range(nmodes)}
        arr_specs = {m: stacks[m].tree_specs(axes) for m in range(nmodes)}
        fac_specs = tuple(P(None, None) for _ in range(nmodes))

        def local_sweep(arrs, facs, norm_x_sq):
            facs = list(facs)
            y = None
            for m in range(nmodes):
                s = stacks[m]
                in_facs = tuple(
                    facs[im][: s.in_rows[n]] for n, im in enumerate(s.in_modes)
                )
                out = _stack_ttmc_call(s, arrs[m], in_facs, in_ranks[m])
                y = jax.lax.psum(out, axes)[: shape[m], : out_cols[m]]
                u = _factor_from_unfolding(y, core_ranks[m])
                facs[m] = (
                    jnp.zeros((prows[m], rps[m]), u.dtype)
                    .at[: shape[m], : core_ranks[m]]
                    .set(u)
                )
            last = nmodes - 1
            u_last = facs[last][: shape[last], : core_ranks[last]]
            core = _core_from_unfolding(y, u_last, last, core_ranks)
            return tuple(facs), core, core_fit_value(core, norm_x_sq)

        def sweep(arrs, facs, norm_x_sq):
            return jax.shard_map(
                local_sweep,
                mesh=mesh,
                in_specs=(arr_specs, fac_specs, P()),
                out_specs=(fac_specs, P(*([None] * nmodes)), P()),
                check_vma=False,
            )(arrs, facs, norm_x_sq)

        return jax.jit(sweep)

    def sweep(self, facs, norm_x_sq):
        """One jitted distributed HOOI iteration in padded space.  Returns
        (new padded factors, core, fit scalar on device) — the
        `PlannedTucker.sweep` contract."""
        return super().sweep(facs, norm_x_sq)


def make_sharded_planned_tucker(
    st: SparseTensor,
    core_ranks: Sequence[int],
    *,
    dist=None,
    devices: int | None = None,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> ShardedPlannedTucker:
    """Build the distributed HOOI workspace: one partition + shard-stacked
    TTMc layout per output mode.  Mirrors `make_sharded_planned_cp_als`;
    with auto_tune=True the sharded PMS scores the TTMc roofline per mode
    (`search_sharded(kernel="ttmc", core_ranks=...)`)."""
    from ..tucker.hooi import _validated_core_ranks

    cr = _validated_core_ranks(st, core_ranks)
    dist = _resolve_dist(dist, devices)
    nshards = dist.dp_size()
    stacks: dict[int, _ShardStack] = {}
    cfgs: dict[int, MemoryControllerConfig] = {}
    for m in range(st.nmodes):
        mcfg = _tuned_cfg(
            st, m, max(cr), nshards, cfg, auto_tune, spec,
            kernel="ttmc", core_ranks=cr,
        )
        cfgs[m] = mcfg
        _, stacks[m] = _sharded_mode_stack(st, m, mcfg, dist, "ttmc")
    return ShardedPlannedTucker(
        stacks=stacks,
        dist=dist,
        shape=st.shape,
        core_ranks=cr,
        cfgs=cfgs,
    )


@dataclasses.dataclass
class ShardedPlannedTT(ShardedWorkspace):
    """Distributed `PlannedTT`: the whole TT-ALS loop on shard-local
    memory-controller layouts — the TT-core mirror of `ShardedPlannedCPALS`
    (same partitions, same stacks, Kronecker-of-two-interfaces kernel,
    per-mode `rank_padded(rl_m*rr_m)` lane contracts).  Per mode, every
    device runs the TT-core kernel on its local layout, ONE psum reassembles
    the right-hand side B_m, and the normal-equations solve runs replicated;
    the fit's per-nnz TT inner product is psum'd over each shard's stream
    slice, like CP's."""

    stacks: dict[int, _ShardStack]
    dist: Any
    shape: tuple[int, ...]
    tt_ranks: tuple[int, ...]  # N-1 interior bond ranks
    cfgs: dict[int, MemoryControllerConfig]
    idx_sh: jax.Array  # (D, max shard nnz, N) fit stream, zero-padded
    val_sh: jax.Array  # (D, max shard nnz)

    @property
    def bond_pairs(self) -> tuple[tuple[int, int], ...]:
        return _tt_bond_pairs(self.tt_ranks, self.nmodes)

    @property
    def lane_ranks(self) -> tuple[int, ...]:
        return tuple(a * b for a, b in self.bond_pairs)

    def in_rank_pairs(self, mode: int) -> tuple[tuple[int, int], ...]:
        pairs = self.bond_pairs
        return tuple(pairs[im] for im in self.stacks[mode].in_modes)

    def _stream_args(self) -> tuple:
        return (self.idx_sh, self.val_sh)

    def _build_sweep(self) -> Callable:
        # Lazy: repro.tt imports this module at load time.
        from ..tt.als import _p_next, _q_suffix, _solve_core, matrix_to_core, tt_inner

        shape, nmodes = self.shape, self.nmodes
        pairs, lr = self.bond_pairs, self.lane_ranks
        rps, prows = self.rank_pads, self.padded_rows
        stacks = self.stacks
        mesh, axes = self.dist.mesh, self.dist.data_axes()
        in_pairs = {m: self.in_rank_pairs(m) for m in range(nmodes)}
        arr_specs = {m: stacks[m].tree_specs(axes) for m in range(nmodes)}
        fac_specs = tuple(P(None, None) for _ in range(nmodes))

        def local_sweep(arrs, idx, val, facs, norm_x_sq):
            facs = list(facs)
            cores = [
                matrix_to_core(facs[m][: shape[m], : lr[m]], *pairs[m])
                for m in range(nmodes)
            ]
            # Right interfaces from the incoming cores (cores > m are
            # untouched until the left-to-right sweep reaches them), the
            # running left interface from each freshly solved core.
            qs = _q_suffix(cores)
            p = jnp.ones((1, 1), jnp.float32)
            for m in range(nmodes):
                s = stacks[m]
                in_mats = tuple(
                    facs[im][: s.in_rows[n]] for n, im in enumerate(s.in_modes)
                )
                out = _stack_ttcore_call(s, arrs[m], in_mats, in_pairs[m], m)
                # The single collective per mode: partial right-hand-side
                # rows from disjoint tile ranges -> the full B_m.
                b = jax.lax.psum(out, axes)[: shape[m], : lr[m]]
                w = _solve_core(jnp.kron(p, qs[m]), b)
                cores[m] = matrix_to_core(w, *pairs[m])
                facs[m] = (
                    jnp.zeros((prows[m], rps[m]), w.dtype)
                    .at[: shape[m], : lr[m]]
                    .set(w)
                )
                p = _p_next(p, cores[m])
            # Fit from psum'd scalars: each shard's slice of <X, TT>
            # (padding entries carry value 0); ||TT||^2 is the completed
            # left-interface chain, a replicated scalar.
            inner = jax.lax.psum(tt_inner(idx[0], val[0], cores), axes)
            resid_sq = jnp.maximum(norm_x_sq + p[0, 0] - 2.0 * inner, 0.0)
            fit = 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)
            return tuple(facs), fit

        def sweep(arrs, idx_sh, val_sh, facs, norm_x_sq):
            facs, fit = jax.shard_map(
                local_sweep,
                mesh=mesh,
                in_specs=(
                    arr_specs,
                    P(axes, None, None),
                    P(axes, None),
                    fac_specs,
                    P(),
                ),
                out_specs=(fac_specs, P()),
                check_vma=False,
            )(arrs, idx_sh, val_sh, facs, norm_x_sq)
            return facs, None, fit

        return jax.jit(sweep)

    def sweep(self, facs, norm_x_sq):
        """One jitted distributed TT-ALS iteration in padded space.  Returns
        (new padded interface matrices, None, fit scalar on device) — the
        `PlannedTT.sweep` contract."""
        return super().sweep(facs, norm_x_sq)


def make_sharded_planned_tt(
    st: SparseTensor,
    tt_ranks: Sequence[int],
    *,
    dist=None,
    devices: int | None = None,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> ShardedPlannedTT:
    """Build the distributed TT-ALS workspace: one partition + shard-stacked
    TT-core layout per output mode.  Mirrors `make_sharded_planned_cp_als`;
    with auto_tune=True the sharded PMS scores the TT roofline per mode
    (`search_sharded(kernel="tt", core_ranks=...)`)."""
    from ..tt.als import _validated_tt_ranks

    tr = _validated_tt_ranks(st, tt_ranks)
    dist = _resolve_dist(dist, devices)
    nshards = dist.dp_size()
    stacks: dict[int, _ShardStack] = {}
    cfgs: dict[int, MemoryControllerConfig] = {}
    part0 = None
    for m in range(st.nmodes):
        mcfg = _tuned_cfg(
            st, m, max(tr), nshards, cfg, auto_tune, spec,
            kernel="tt", core_ranks=tr,
        )
        cfgs[m] = mcfg
        part, stacks[m] = _sharded_mode_stack(st, m, mcfg, dist, "tt")
        if m == 0:
            part0 = part
    idx_sh, val_sh = _stack_fit_stream(part0, st.shape, dist)
    return ShardedPlannedTT(
        stacks=stacks,
        dist=dist,
        shape=st.shape,
        tt_ranks=tr,
        cfgs=cfgs,
        idx_sh=idx_sh,
        val_sh=val_sh,
    )
