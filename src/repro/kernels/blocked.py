"""The one kernel template behind MTTKRP, TTMc and the TT-core update.

All three kernels walk the same BlockPlan layout (core/remap.py) the same
way; they differ only in the per-element product of the gathered rows.
This module owns everything else, so each kernel module supplies just that
product (a `contract(rows) -> (blk, out_cols)` function):

  * DMA Engine   — the non-zero stream arrives as `(None, 1, blk)` blocks of
                   `(nblocks, 1, blk)` arrays (the second-minor block
                   dimension equals the array's, which the TPU tiling
                   accepts); Pallas double-buffers consecutive grid steps.
  * Cache Engine — one `(tile_n, width_n)` factor tile per input mode,
                   selected by scalar-prefetched tile ids; Pallas skips the
                   copy when the id repeats.  Rows are gathered from the
                   VMEM tile by a one-hot `(tile_n, blk)^T @ (tile_n, width)`
                   matmul on the MXU.
  * Approach 1   — blocks are sorted by output tile, so an accumulator tile
                   is resident across its run and written back once.
  * MXU          — the segment sum is a value-weighted one-hot
                   `(tile_i, blk) @ (blk, out_cols)` matmul.

Every dot runs at `Precision.HIGHEST`: the one-hot operands are exact in
bf16, but the factor values are f32 and a default-precision MXU pass would
round them to bf16.

SMEM chunking.  The tile-id streams are scalar-prefetched, so each call
holds `(1 + n_in)` int32 per grid step in SMEM.  A mode with more blocks
than fit is run as a chain of calls over consecutive block ranges
(`chunk_blocks`); every call reads its accumulator tiles from the previous
call's output (aliased in place), so an output tile whose run crosses a
chunk boundary keeps accumulating, and a tile no block visits keeps the
zeros the chain starts from.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import device_spec, interpret_mode

__all__ = ["HIGHEST", "blocked_call", "chunk_blocks", "dot", "spread"]

HIGHEST = jax.lax.Precision.HIGHEST

# Fraction of SMEM the tile-id streams of one call may take; the rest is
# left to the pipeline's own scalars.
SMEM_HEADROOM = 0.5


def chunk_blocks(n_streams: int) -> int:
    """Most grid steps one call may take: `n_streams` int32 tile-id streams
    must fit the chip's SMEM with headroom."""
    return max(1, int(device_spec().smem_bytes * SMEM_HEADROOM) // (4 * n_streams))


def _vmem_limit_bytes() -> int:
    """The scoped-VMEM limit every kernel compiles with: the PMS budget
    (`TPUSpec.vmem_bytes * vmem_usable_frac`) of the chip, so any
    configuration the PMS admits is also one the compiler grants."""
    spec = device_spec()
    return int(spec.vmem_bytes * spec.vmem_usable_frac)


def dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def spread(rows: int, cols: int, *, width: int, stride: int, count: int,
           transpose: bool = False) -> jax.Array:
    """0/1 matrix E (rows, cols) with E[j, c] = 1 iff c < width and
    (c // stride) % count == j — or with the roles of the axes swapped when
    `transpose`.  `x @ E` spreads the columns of x into a Kronecker column
    layout without a lane-splitting reshape: stride = the product of the
    faster factors' widths, count = x's own width."""
    jdim, cdim = (1, 0) if transpose else (0, 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), jdim)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), cdim)
    hit = (c < width) & (jax.lax.rem(jax.lax.div(c, stride), count) == j)
    return hit.astype(jnp.float32)


def _gather(loc: jax.Array, tile: jax.Array) -> jax.Array:
    """Rows `loc` (1, blk) of a VMEM tile (tile_n, w) as (blk, w): a one-hot
    matmul, transposed on the MXU so that `loc` stays a lane vector."""
    onehot_t = jax.lax.broadcasted_iota(jnp.int32, (tile.shape[0], loc.shape[1]), 0) == loc
    return jax.lax.dot_general(
        onehot_t.astype(jnp.float32), tile.astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )


def _kernel(contract, tile_i: int, n_in: int, off_ref, it_ref, *refs):
    """refs after the scalar prefetches (offset, output tile ids):
      [0 : n_in]               input tile ids   (used by the index maps only)
      [n_in]                   vals_ref         (1, blk)
      [n_in+1]                 iloc_ref         (1, blk)
      [n_in+2 : 2n_in+2]       input local idx  (1, blk) each
      [2n_in+2 : 3n_in+2]      factor tiles     (tile_n, width_n) each
      [3n_in+2]                acc_ref          (tile_i, out_cols) carried in
      [3n_in+3]                out_ref          (tile_i, out_cols)
    """
    del off_ref
    vals_ref, iloc_ref = refs[n_in], refs[n_in + 1]
    loc_refs = refs[n_in + 2 : 2 * n_in + 2]
    fac_refs = refs[2 * n_in + 2 : 3 * n_in + 2]
    acc_ref, out_ref = refs[3 * n_in + 2], refs[3 * n_in + 3]

    b = pl.program_id(0)
    first_visit = jnp.logical_or(b == 0, it_ref[b] != it_ref[jnp.maximum(b - 1, 0)])

    @pl.when(first_visit)
    def _():
        out_ref[...] = acc_ref[...]

    rows = [_gather(l[...], f[...]) for l, f in zip(loc_refs, fac_refs)]
    contrib = contract(rows)  # (blk, out_cols)
    blk = contrib.shape[0]
    seg = jax.lax.broadcasted_iota(jnp.int32, (tile_i, blk), 0) == iloc_ref[...]
    weighted = jnp.where(seg, vals_ref[...].astype(jnp.float32), 0.0)
    out_ref[...] += dot(weighted, contrib)


def _chunk_call(contract, offset, ids, streams, factors, acc, *, tile_i,
                in_tiles, interpret):
    n_in = len(in_tiles)
    blk = streams[0].shape[-1]
    out_rows, out_cols = acc.shape

    def stream_spec():
        return pl.BlockSpec((None, 1, blk), lambda b, off, *ids: (off[0] + b, 0, 0))

    def factor_spec(n):
        return pl.BlockSpec(
            (in_tiles[n], factors[n].shape[1]),
            lambda b, off, it, *ts, n=n: (ts[n][b], 0),
        )

    acc_spec = pl.BlockSpec((tile_i, out_cols), lambda b, off, it, *ts: (it[b], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + n_in,  # offset, output ids, one per input
        grid=(ids[0].shape[0],),
        in_specs=(
            [stream_spec() for _ in streams]
            + [factor_spec(n) for n in range(n_in)]
            + [acc_spec]
        ),
        out_specs=acc_spec,
    )
    return pl.pallas_call(
        functools.partial(_kernel, contract, tile_i, n_in),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_rows, out_cols), jnp.float32),
        input_output_aliases={2 + n_in + len(streams) + n_in: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit_bytes()),
        interpret=interpret,
    )(offset, *ids, *streams, *factors, acc)


def blocked_call(
    contract: Callable[[list], jax.Array],
    block_it: jax.Array,
    block_in: Sequence[jax.Array],
    vals: jax.Array,
    iloc: jax.Array,
    in_locs: Sequence[jax.Array],
    factors_pad: Sequence[jax.Array],
    *,
    tile_i: int,
    in_tiles: tuple[int, ...],
    out_rows: int,
    out_cols: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Run one planned kernel over a whole BlockPlan layout.

    Stream arrays are `(nblocks, 1, blk)`; tile-id streams `(nblocks,)`.
    `contract` maps the gathered rows — one `(blk, width_n)` array per input
    mode, in plan.in_modes order — to the per-element `(blk, out_cols)`
    contribution before value weighting.  Returns the `(out_rows, out_cols)`
    float32 accumulator; rows of tiles no block visits are zero.
    `interpret=None` decides from the platform."""
    if interpret is None:
        interpret = interpret_mode()
    block_in, in_locs, factors_pad = tuple(block_in), tuple(in_locs), tuple(factors_pad)
    n_in = len(in_tiles)
    if not len(block_in) == len(in_locs) == len(factors_pad) == n_in:
        raise ValueError(
            f"expected {n_in} input tile-id streams, local-index streams and "
            f"factors, got {len(block_in)}, {len(in_locs)}, {len(factors_pad)}"
        )
    streams = (vals, iloc) + in_locs
    nblocks = block_it.shape[0]
    step = chunk_blocks(1 + n_in)
    acc = jnp.zeros((out_rows, out_cols), jnp.float32)
    for start in range(0, nblocks, step):
        stop = min(nblocks, start + step)
        ids = (block_it[start:stop],) + tuple(t[start:stop] for t in block_in)
        acc = _chunk_call(
            contract, jnp.full((1,), start, jnp.int32), ids, streams,
            factors_pad, acc, tile_i=tile_i, in_tiles=in_tiles,
            interpret=interpret,
        )
    return acc
