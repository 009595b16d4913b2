"""Tensor Remapper (paper Alg. 5 + Sec. 5.1.3), adapted to TPU.

The paper remaps (re-sorts) the non-zero stream into the *output mode's*
order before each mode's MTTKRP, so Approach 1 (no DRAM partial sums) applies
to every mode with a single tensor copy.  The FPGA mechanism is a table of
per-output-coordinate *address pointers* (a counting sort); when the table
exceeds on-chip memory the paper flags it as a key design problem.

TPU adaptation:
  * `remap_stable`           — XLA stable sort (production path, jittable).
  * `remap_pointer_machine`  — faithful pointer-table emulation (lax.scan FIFO,
                               one element per step) used to *validate* that the
                               sort path implements exactly the paper's mapping.
  * `remap_radix`            — hierarchical counting sort for when the pointer
                               table exceeds the budget (paper's overflow case):
                               digits of `pointer_budget` bins per pass.
  * `plan_blocks`            — two-level *tile* remap producing the Pallas
                               kernel's memory layout: blocks sorted by
                               (output tile, input tile id tuple) with
                               per-block metadata, for any order >= 3. This is
                               the "ideal memory layout" of Sec. 3.1 (bounded
                               pointer table + equal-sized partitions).
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .coo import SparseTensor

__all__ = [
    "pointer_table",
    "remap_stable",
    "remap_pointer_machine",
    "remap_radix",
    "radix_digits",
    "BlockPlan",
    "group_key",
    "plan_blocks",
    "plan_blocks_reference",
]


def pointer_table(coords: jax.Array, nbins: int) -> tuple[jax.Array, jax.Array]:
    """The paper's address-pointer table: per-bin base addresses.

    Returns (offsets, counts): offsets[b] = where bin b's first element goes
    (exclusive prefix sum of the histogram)."""
    counts = jnp.zeros((nbins,), jnp.int32).at[coords].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    return offsets, counts


@partial(jax.jit, static_argnames=("mode",))
def remap_stable(indices: jax.Array, values: jax.Array, mode: int):
    """Stable sort of the COO stream by one mode's coordinates.

    Production remap: XLA's sort is the TPU-native equivalent of the streaming
    counting sort (same output order — stability preserves the FIFO property
    the paper's weak-consistency model requires).
    Returns (indices_sorted, values_sorted, perm)."""
    perm = jnp.argsort(indices[:, mode], stable=True)
    return indices[perm], values[perm], perm


def remap_pointer_machine(indices: np.ndarray, values: np.ndarray, mode: int, nbins: int):
    """Paper-faithful Tensor Remapper emulation: stream elements one by one,
    looking up + bumping the per-output-coordinate address pointer (Alg. 5
    lines 3-6).  Host-side (numpy); used in tests to certify `remap_stable`
    produces the identical layout."""
    coords = indices[:, mode]
    counts = np.bincount(coords, minlength=nbins)
    ptr = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)
    out_idx = np.empty_like(indices)
    out_val = np.empty_like(values)
    for z in range(indices.shape[0]):  # the element-wise store stream
        c = coords[z]
        p = ptr[c]
        out_idx[p] = indices[z]
        out_val[p] = values[z]
        ptr[c] = p + 1
    return out_idx, out_val


def radix_digits(nbins: int, pointer_budget: int) -> int:
    """Number of counting-sort passes so that pointer_budget**ndigits >= nbins.

    Pure integer arithmetic: the float formulation
    ceil(log(nbins)/log(budget)) is off by one at exact powers of the budget
    (log(64)/log(4) = 3.0000000000000004 -> 4 passes instead of 3)."""
    assert pointer_budget >= 2, "need at least two bins per pass"
    ndigits, span = 1, pointer_budget
    while span < nbins:
        span *= pointer_budget
        ndigits += 1
    return ndigits


@partial(jax.jit, static_argnames=("mode", "nbins", "pointer_budget"))
def remap_radix(indices: jax.Array, values: jax.Array, mode: int, nbins: int, pointer_budget: int):
    """Hierarchical remap for pointer tables larger than on-chip memory
    (paper Sec. 3.1: 10M-coordinate modes need 40 MB of pointers).

    Runs radix_digits(nbins, budget) stable counting-sort passes,
    least-significant digit first, with at most `pointer_budget` pointers live
    per pass — the direct analogue of splitting the sort into on-chip-sized
    rounds."""
    ndigits = radix_digits(max(nbins, 2), pointer_budget)
    coords = indices[:, mode]
    order = jnp.arange(coords.shape[0])
    key = coords
    for _ in range(ndigits):
        digit = key % pointer_budget
        p = jnp.argsort(digit, stable=True)  # counting-sort pass with <= budget bins
        order = order[p]
        key = key[p] // pointer_budget
    return indices[order], values[order], order


# ---------------------------------------------------------------------------
# Tile-level block plan for the Pallas kernel (the "memory layout")
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BlockPlan:
    """Kernel memory layout: the remapped non-zero stream plus per-block tile
    metadata.  All arrays host-side numpy; `to_device` happens in ops.py.

    Layout contract (consumed by kernels/mttkrp_pallas.py):
      * non-zeros are grouped into blocks of `blk` elements;
      * blocks are sorted by (output tile, then input tile id-tuple) —
        Approach 1 at tile granularity, so each output tile's blocks are
        contiguous;
      * within a block every element's coordinates fall inside the block's
        (it, t_0, ..., t_{N-2}) tiles; local indices are precomputed;
      * padding elements have value 0 (and local index 0).

    N-mode: the N-1 *input* modes each carry one tile-id stream
    (`block_in[n]`) and one local-index vector (`in_locs[n]`).  For 3-mode
    tensors the legacy `jt`/`kt` names are provided as views.
    """

    vals: np.ndarray  # (nblocks*blk,) f32
    iloc: np.ndarray  # (nblocks*blk,) int32 — output-row index within tile
    in_locs: tuple[np.ndarray, ...]  # N-1 x (nblocks*blk,) int32
    block_it: np.ndarray  # (nblocks,) int32
    block_in: tuple[np.ndarray, ...]  # N-1 x (nblocks,) int32
    tile_i: int
    in_tiles: tuple[int, ...]  # N-1 input-mode tile sizes
    blk: int
    out_rows: int  # padded I_out (multiple of tile_i)
    in_rows: tuple[int, ...]  # N-1 padded input-mode row counts
    mode: int
    in_modes: tuple[int, ...]
    nnz: int  # true nnz before padding

    @property
    def nblocks(self) -> int:
        return self.block_it.shape[0]

    @property
    def n_in(self) -> int:
        return len(self.in_modes)

    # --- 3-mode legacy views (every tensor has >= 2 input modes) ---
    @property
    def jloc(self) -> np.ndarray:
        return self.in_locs[0]

    @property
    def kloc(self) -> np.ndarray:
        return self.in_locs[1]

    @property
    def block_jt(self) -> np.ndarray:
        return self.block_in[0]

    @property
    def block_kt(self) -> np.ndarray:
        return self.block_in[1]

    @property
    def tile_j(self) -> int:
        return self.in_tiles[0]

    @property
    def tile_k(self) -> int:
        return self.in_tiles[1]

    @property
    def rows_j(self) -> int:
        return self.in_rows[0]

    @property
    def rows_k(self) -> int:
        return self.in_rows[1]

    # --- locality statistics (feed the PMS / Cache-Engine model) ---
    def tile_fills(self, chunk: int | None = None) -> dict[str, int]:
        """Number of HBM->VMEM tile fetches Pallas will issue: a tile is
        re-fetched only when the block's tile id *changes* between consecutive
        grid steps (Pallas skips the copy when the index map is unchanged —
        the run-length structure of the plan IS the cache).  With `chunk`,
        the grid runs as calls of at most `chunk` steps, and each call
        fetches every tile afresh at its first step.

        Keys: "A" for the output accumulator tile, then one letter per input
        mode ("B", "C", "D", "E", ...)."""

        def fills(ids: np.ndarray) -> int:
            if ids.size == 0:
                return 0
            fresh = ids[1:] != ids[:-1]
            if chunk is not None:
                fresh |= np.arange(1, ids.size) % chunk == 0
            return int(1 + np.count_nonzero(fresh))

        out = {"A": fills(self.block_it)}
        for n, ids in enumerate(self.block_in):
            out[chr(ord("B") + n)] = fills(ids)
        return out

    def padding_fraction(self) -> float:
        return 1.0 - self.nnz / float(self.vals.shape[0]) if self.vals.size else 0.0

    def a_tile_single_flush(self) -> bool:
        """Approach-1 invariant: each output tile's blocks are contiguous."""
        it = self.block_it
        seen_last = {}
        for pos, t in enumerate(it):
            if t in seen_last and seen_last[t] != pos - 1:
                return False
            seen_last[t] = pos
        return True


class PlanValidationError(ValueError):
    """A BlockPlan violates its layout contract (see `validate_plan`)."""


def plans_validated() -> bool:
    """True when `REPRO_VALIDATE_PLANS` requests integrity validation of
    every plan at build time and on plan-cache hits.  Read per call, so tests
    (and tenants) can flip it without re-importing."""
    return os.environ.get("REPRO_VALIDATE_PLANS", "").strip().lower() in (
        "1", "true", "yes", "on",
    )


def validate_plan(plan: BlockPlan) -> BlockPlan:
    """Assert every BlockPlan invariant of the layout contract; raise
    `PlanValidationError` naming the first violation.  Opt-in on the hot
    paths via `REPRO_VALIDATE_PLANS=1` (`plans_validated`); always available
    directly for debugging a suspect layout.  Returns the plan for chaining.

    Invariants checked:
      * stream arrays span exactly `nblocks * blk` slots, one tile-id stream
        and one local-index vector per input mode;
      * padded row counts are tile-aligned and cover the true rows;
      * values are finite; at most `nnz` slots are non-zero (padding slots
        carry value 0) and the stream has room for all `nnz` non-zeros;
      * local indices lie inside their tile (`0 <= iloc < tile_i`,
        `0 <= in_locs[n] < in_tiles[n]`);
      * block tile ids are in range for the padded row counts;
      * Approach-1 contiguity: each output tile's blocks are contiguous
        (`a_tile_single_flush`).
    """

    def fail(msg: str):
        raise PlanValidationError(
            f"BlockPlan(mode={plan.mode}, nnz={plan.nnz}): {msg}"
        )

    n_in = plan.n_in
    if not (len(plan.in_locs) == len(plan.block_in) == len(plan.in_tiles)
            == len(plan.in_rows) == n_in):
        fail("inconsistent input-mode arity across "
             "in_locs/block_in/in_tiles/in_rows/in_modes")
    if plan.mode in plan.in_modes or len(set(plan.in_modes)) != n_in:
        fail(f"in_modes {plan.in_modes} must be distinct and exclude the "
             f"output mode {plan.mode}")
    if plan.blk < 1:
        fail(f"blk={plan.blk} must be >= 1")
    total = plan.nblocks * plan.blk
    for name, arr in (("vals", plan.vals), ("iloc", plan.iloc),
                      *((f"in_locs[{n}]", plan.in_locs[n]) for n in range(n_in))):
        if arr.shape != (total,):
            fail(f"{name} has shape {arr.shape}, expected ({total},) "
                 f"= nblocks*blk")
    for n in range(n_in):
        if plan.block_in[n].shape != (plan.nblocks,):
            fail(f"block_in[{n}] has shape {plan.block_in[n].shape}, "
                 f"expected ({plan.nblocks},)")
    if plan.out_rows % plan.tile_i != 0:
        fail(f"out_rows={plan.out_rows} not a multiple of tile_i={plan.tile_i}")
    for n in range(n_in):
        if plan.in_rows[n] % plan.in_tiles[n] != 0:
            fail(f"in_rows[{n}]={plan.in_rows[n]} not a multiple of "
                 f"in_tiles[{n}]={plan.in_tiles[n]}")
    if not np.all(np.isfinite(plan.vals)):
        fail("non-finite values in the remapped stream")
    if total < plan.nnz:
        fail(f"stream holds {total} slots but the plan claims nnz={plan.nnz}")
    nz = int(np.count_nonzero(plan.vals))
    if nz > plan.nnz:
        fail(f"{nz} non-zero slots exceed nnz={plan.nnz} — padding slots "
             f"must be zero-valued")
    if plan.iloc.size and (plan.iloc.min() < 0 or plan.iloc.max() >= plan.tile_i):
        fail(f"iloc out of tile bounds [0, {plan.tile_i}): "
             f"range [{plan.iloc.min()}, {plan.iloc.max()}]")
    for n in range(n_in):
        loc = plan.in_locs[n]
        if loc.size and (loc.min() < 0 or loc.max() >= plan.in_tiles[n]):
            fail(f"in_locs[{n}] out of tile bounds [0, {plan.in_tiles[n]}): "
                 f"range [{loc.min()}, {loc.max()}]")
    ntiles = plan.out_rows // plan.tile_i
    if plan.block_it.size and (plan.block_it.min() < 0
                               or plan.block_it.max() >= ntiles):
        fail(f"block_it out of range [0, {ntiles}): "
             f"range [{plan.block_it.min()}, {plan.block_it.max()}]")
    for n in range(n_in):
        nt = plan.in_rows[n] // plan.in_tiles[n]
        bt = plan.block_in[n]
        if bt.size and (bt.min() < 0 or bt.max() >= nt):
            fail(f"block_in[{n}] out of range [0, {nt}): "
                 f"range [{bt.min()}, {bt.max()}]")
    if not plan.a_tile_single_flush():
        fail("Approach-1 contiguity violated: an output tile's blocks are "
             "not contiguous")
    return plan


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _ceil_div(x: int, m: int) -> int:
    return max(1, (x + m - 1) // m)


def group_key(tile_cols: list[np.ndarray], tile_counts: list[int]) -> np.ndarray:
    """Mixed-radix encoding of per-mode tile ids into one collision-free
    int64 key.  `tile_counts[m]` is the explicit per-mode tile count
    (ceil(shape/tile)); every id in `tile_cols[m]` must be < tile_counts[m],
    so two distinct id tuples can never alias."""
    assert len(tile_cols) == len(tile_counts)
    radix = math.prod(int(c) for c in tile_counts)
    if radix > np.iinfo(np.int64).max:
        raise OverflowError(
            f"group_key radix {radix} overflows int64: tile counts "
            f"{tuple(tile_counts)} — use larger tiles for the big modes"
        )
    key = np.zeros_like(tile_cols[0], dtype=np.int64)
    for col, count in zip(tile_cols, tile_counts):
        assert count >= 1
        key = key * np.int64(count) + col.astype(np.int64)
    return key


def default_in_tiles(n_in: int, tile_j: int, tile_k: int) -> tuple[int, ...]:
    """Expand the legacy (tile_j, tile_k) pair to N-1 input tile sizes.
    The expansion policy lives in CacheEngineConfig.input_tiles — this is a
    convenience wrapper so plan_blocks' default never diverges from what the
    PMS scores."""
    from .memctrl import CacheEngineConfig  # local: keep remap importable alone

    return CacheEngineConfig(tile_j=tile_j, tile_k=tile_k).input_tiles(n_in)


@dataclasses.dataclass
class _GroupedStream:
    """Shared prologue of the layout build: the remap permutation plus the
    group geometry, with the stream arrays kept in *original* order.  Both
    the vectorized production build and the loop reference consume this; the
    reference gathers full sorted copies (part of its per-element cost), the
    vectorized build gathers only what it scatters."""

    order: np.ndarray  # the remap permutation (stable sort by group key)
    i: np.ndarray  # output-mode coordinates, original order (int64)
    ins: list[np.ndarray]  # input-mode coordinates, original order
    v: np.ndarray  # values, original order
    it: np.ndarray  # output tile ids, original order
    in_ts: list[np.ndarray]  # input tile ids, original order
    boundaries: np.ndarray  # first *sorted* position of each group
    group_sizes: np.ndarray
    padded_sizes: np.ndarray  # group sizes rounded up to a multiple of blk
    in_modes: tuple[int, ...]
    in_tiles: tuple[int, ...]

    @property
    def total(self) -> int:
        return int(self.padded_sizes.sum())


def _grouped_stream(
    st: SparseTensor,
    mode: int,
    tile_i: int,
    tile_j: int,
    tile_k: int,
    blk: int,
    in_tiles: tuple[int, ...] | None,
) -> _GroupedStream:
    assert st.nmodes >= 3, "kernel block plan needs >= 3-mode tensors"
    in_modes = tuple(m for m in range(st.nmodes) if m != mode)
    n_in = len(in_modes)
    if in_tiles is None:
        in_tiles = default_in_tiles(n_in, tile_j, tile_k)
    assert len(in_tiles) == n_in
    i = st.indices[:, mode].astype(np.int64)
    ins = [st.indices[:, m].astype(np.int64) for m in in_modes]
    v = st.values

    it = i // tile_i
    in_ts = [c // t for c, t in zip(ins, in_tiles)]
    # Remap: sort by (output tile, input tile tuple).  The collision-free
    # mixed-radix group key IS that tuple in lexicographic order, so one
    # stable argsort on it replaces an N-key lexsort (~2x cheaper) while
    # producing the identical permutation; stability preserves prior order
    # within a tile tuple.  Explicit per-mode tile counts keep the key
    # collision-free.
    n_tiles = [_ceil_div(st.shape[mode], tile_i)] + [
        _ceil_div(st.shape[m], t) for m, t in zip(in_modes, in_tiles)
    ]
    key = group_key([it] + in_ts, n_tiles)
    order = np.argsort(key, kind="stable")
    key_sorted = key[order]

    # Group boundaries over identical (it, t_0, ..., t_{N-2}) tuples.
    boundaries = np.flatnonzero(
        np.concatenate([[True], key_sorted[1:] != key_sorted[:-1]])
    )
    group_sizes = np.diff(np.concatenate([boundaries, [key_sorted.size]]))
    padded_sizes = np.maximum(_ceil_to(1, blk), ((group_sizes + blk - 1) // blk) * blk)
    return _GroupedStream(
        order=order,
        i=i,
        ins=ins,
        v=v,
        it=it,
        in_ts=in_ts,
        boundaries=boundaries,
        group_sizes=group_sizes,
        padded_sizes=padded_sizes,
        in_modes=in_modes,
        in_tiles=tuple(in_tiles),
    )


def _assemble_plan(
    st: SparseTensor,
    mode: int,
    g: _GroupedStream,
    tile_i: int,
    blk: int,
    vals: np.ndarray,
    iloc: np.ndarray,
    in_locs: list[np.ndarray],
    block_it: np.ndarray,
    block_in: list[np.ndarray],
) -> BlockPlan:
    plan = BlockPlan(
        vals=vals,
        iloc=iloc,
        in_locs=tuple(in_locs),
        block_it=block_it,
        block_in=tuple(block_in),
        tile_i=tile_i,
        in_tiles=g.in_tiles,
        blk=blk,
        out_rows=_ceil_to(st.shape[mode], tile_i),
        in_rows=tuple(
            _ceil_to(st.shape[m], t) for m, t in zip(g.in_modes, g.in_tiles)
        ),
        mode=mode,
        in_modes=g.in_modes,
        nnz=st.nnz,
    )
    # Opt-in build-time integrity gate (REPRO_VALIDATE_PLANS=1): both the
    # vectorized and the reference builder funnel through this assembly tail.
    if plans_validated():
        validate_plan(plan)
    return plan


def _record_plan_metrics(plan: BlockPlan, dt: float, builder: str) -> None:
    """Layout statistics every build records (docs/observability.md): build
    wall time, padding of the padded stream, block count, and the
    blocks-per-output-tile imbalance (max over occupied tiles / mean — the
    skew the Cache Engine's A-tile residency sees)."""
    pad = plan.padding_fraction()
    _metrics.histogram("plan.build_seconds", builder=builder).observe(dt)
    _metrics.histogram("plan.padding_fraction").observe(pad)
    _metrics.histogram("plan.nblocks").observe(plan.nblocks)
    if plan.block_it.size:
        per_tile = np.bincount(plan.block_it)
        per_tile = per_tile[per_tile > 0]
        _metrics.histogram("plan.tile_block_imbalance").observe(
            float(per_tile.max() / per_tile.mean())
        )


def plan_blocks(
    st: SparseTensor,
    mode: int,
    *,
    tile_i: int = 256,
    tile_j: int = 256,
    tile_k: int = 256,
    blk: int = 256,
    in_tiles: tuple[int, ...] | None = None,
) -> BlockPlan:
    """Two-level tile remap (host-side preprocessing == the Tensor Remapper +
    memory-layout generator).  Supports any order >= 3 (paper Table 2 has
    3–5-mode tensors): the N-1 input modes each get a tile-id stream and a
    local-index vector.  `in_tiles` overrides the per-input-mode tile sizes;
    by default the first input mode uses tile_j and the rest tile_k.

    Vectorized build: one fancy-index scatter moves every non-zero to its
    padded destination (cumsum of padded group sizes -> per-group destination
    offsets), and `np.repeat` expands per-group tile ids to per-block
    metadata.  Local indices are computed in original stream order and
    gathered through the remap permutation, so no fully-sorted copies of the
    coordinate arrays are ever materialized.  Bit-identical to
    `plan_blocks_reference` (the per-group Python loop it replaced), which is
    kept for parity testing; the vectorized path is what makes layout
    generation cheap enough to amortize (paper Sec. 3.1 treats layout-build
    cost as a first-class quantity)."""
    t0 = time.perf_counter()
    with _trace.span("plan_build", mode=mode, builder="vectorized",
                     nnz=st.nnz, blk=blk):
        g = _grouped_stream(st, mode, tile_i, tile_j, tile_k, blk, in_tiles)
        n_in = len(g.in_modes)
        total = g.total
        nnz = g.i.size
        order = g.order

        # Destination of each sorted non-zero: its group's padded base offset
        # plus its rank within the group.
        dst_off = np.concatenate([[0], np.cumsum(g.padded_sizes)[:-1]])
        # per-element group id via boundary flags (O(nnz), no repeat allocation)
        flags = np.zeros((nnz,), np.int64)
        flags[g.boundaries[1:]] = 1
        gid = np.cumsum(flags)
        dest = dst_off[gid] + (np.arange(nnz, dtype=np.int64) - g.boundaries[gid])

        vals = np.zeros((total,), np.float32)
        iloc = np.zeros((total,), np.int32)
        in_locs = [np.zeros((total,), np.int32) for _ in range(n_in)]
        vals[dest] = g.v[order]
        iloc[dest] = (g.i - g.it * tile_i).astype(np.int32)[order]
        for n in range(n_in):
            in_locs[n][dest] = (g.ins[n] - g.in_ts[n] * g.in_tiles[n]).astype(np.int32)[order]

        # Per-block tile-id metadata: each group contributes padded_size/blk
        # identical blocks; `leaders` are the original positions of each
        # group's first sorted element.
        nb_per_group = g.padded_sizes // blk
        leaders = order[g.boundaries]
        block_it = np.repeat(g.it[leaders], nb_per_group).astype(np.int32)
        block_in = [
            np.repeat(t[leaders], nb_per_group).astype(np.int32) for t in g.in_ts
        ]
        plan = _assemble_plan(
            st, mode, g, tile_i, blk, vals, iloc, in_locs, block_it, block_in
        )
    _record_plan_metrics(plan, time.perf_counter() - t0, "vectorized")
    return plan


def plan_blocks_reference(
    st: SparseTensor,
    mode: int,
    *,
    tile_i: int = 256,
    tile_j: int = 256,
    tile_k: int = 256,
    blk: int = 256,
    in_tiles: tuple[int, ...] | None = None,
) -> BlockPlan:
    """Per-group Python-loop layout build: the original O(#groups)
    interpreter-loop implementation, kept as the executable specification
    `plan_blocks` must match bit-for-bit (see the hypothesis parity property
    in tests/test_remap.py)."""
    t0 = time.perf_counter()
    with _trace.span("plan_build", mode=mode, builder="reference",
                     nnz=st.nnz, blk=blk):
        g = _grouped_stream(st, mode, tile_i, tile_j, tile_k, blk, in_tiles)
        n_in = len(g.in_modes)
        total = g.total
        nblocks = total // blk

        # The loop walks the stream in sorted order: materialize sorted copies.
        order = g.order
        i, v, it = g.i[order], g.v[order], g.it[order]
        ins = [c[order] for c in g.ins]
        in_ts = [t[order] for t in g.in_ts]

        vals = np.zeros((total,), np.float32)
        iloc = np.zeros((total,), np.int32)
        in_locs = [np.zeros((total,), np.int32) for _ in range(n_in)]
        block_it = np.empty((nblocks,), np.int32)
        block_in = [np.empty((nblocks,), np.int32) for _ in range(n_in)]

        src = 0
        dst = 0
        b = 0
        for gsize, psize in zip(g.group_sizes, g.padded_sizes):
            s, e = src, src + gsize
            vals[dst : dst + gsize] = v[s:e]
            iloc[dst : dst + gsize] = (i[s:e] - it[s] * tile_i).astype(np.int32)
            for n in range(n_in):
                in_locs[n][dst : dst + gsize] = (
                    ins[n][s:e] - in_ts[n][s] * g.in_tiles[n]
                ).astype(np.int32)
            nb = psize // blk
            block_it[b : b + nb] = it[s]
            for n in range(n_in):
                block_in[n][b : b + nb] = in_ts[n][s]
            src = e
            dst += psize
            b += nb

        plan = _assemble_plan(
            st, mode, g, tile_i, blk, vals, iloc, in_locs, block_it, block_in
        )
    _record_plan_metrics(plan, time.perf_counter() - t0, "reference")
    return plan
