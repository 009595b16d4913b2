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
                   VMEM tile by a one-hot `(blk, tile_n) @ (tile_n, width)`
                   matmul on the MXU.
  * Approach 1   — blocks are sorted by output tile, so an accumulator tile
                   is resident across its run and written back once.
  * MXU          — the segment sum is a one-hot `(tile_i, blk) @ (blk,
                   out_cols)` matmul of the value-weighted contributions.

Exact bf16 passes.  In every matmul here one operand is exactly 0 or 1 (a
one-hot or a `spread` matrix), so it is exact in bf16.  The other, real
operand is f32, and any finite f32 is the exact sum of three bf16 pieces
(`pieces`: 8 + 8 + 8 significand bits), so `dot01` runs one single-pass
bf16 MXU matmul per piece with float32 accumulation.  Every product is then
exact and the sums accumulate in float32: the float32 result in three
passes, where the MXU's own float32 emulation takes six and still drops the
low-by-low terms.  A real operand already in bf16 is one piece, one pass.
The segment sum keeps its one-hot 0/1 by multiplying the values into the
contribution first (an f32 multiply on the VPU).  The two lane vectors a
step needs down its rows, the local indices of a gather and the values,
get there by a transpose (`_rows`), which moves data without arithmetic.

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

__all__ = ["blocked_call", "chunk_blocks", "dot01", "pieces", "spread"]


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


def _top8(x: jax.Array) -> jax.Array:
    """f32 `x` cut to its sign, exponent and top 8 significand bits (the
    low 16 bits of its pattern zeroed): a bf16 value, held as f32."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)  # 0xFFFF0000
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def pieces(x: jax.Array) -> tuple[jax.Array, ...]:
    """bf16 arrays whose float32 sum, in order, is `x` bit for bit, for any
    finite `x`: `x` itself when it is bf16, else hi, mid and lo.  hi is x's
    top 8 significand bits, mid the top 8 of the remainder x - hi (at most
    16 bits), lo the rest (at most 8); every piece converts to bf16 and
    every subtraction is exact."""
    if x.dtype == jnp.bfloat16:
        return (x,)
    hi = _top8(x)
    r = x - hi
    mid = _top8(r)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, r - mid))


def dot01(a: jax.Array, b: jax.Array) -> jax.Array:
    """`a @ b` in float32, exactly, where one operand is 0/1 in bf16 and the
    other is real: one single-pass bf16 MXU matmul per bf16 piece of the
    real operand, summed in float32.  Both in bf16 is one pass."""
    real_left = b.dtype == jnp.bfloat16
    real, e01 = (a, b) if real_left else (b, a)
    if e01.dtype != jnp.bfloat16:
        raise TypeError(f"dot01 needs a bf16 0/1 operand, got {a.dtype} and {b.dtype}")
    out = None
    for piece in pieces(real):
        term = jnp.dot(*((piece, e01) if real_left else (e01, piece)),
                       # one pass, also under a caller's default_matmul_precision
                       precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)
        out = term if out is None else out + term
    return out


def spread(rows: int, cols: int, *, width: int, stride: int, count: int,
           transpose: bool = False) -> jax.Array:
    """0/1 bf16 matrix E (rows, cols) with E[j, c] = 1 iff c < width and
    (c // stride) % count == j — or with the roles of the axes swapped when
    `transpose`.  `x @ E` spreads the columns of x into a Kronecker column
    layout without a lane-splitting reshape: stride = the product of the
    faster factors' widths, count = x's own width."""
    jdim, cdim = (1, 0) if transpose else (0, 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), jdim)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), cdim)
    hit = (c < width) & (jax.lax.rem(jax.lax.div(c, stride), count) == j)
    return hit.astype(jnp.bfloat16)


def _rows(v: jax.Array, width: int) -> jax.Array:
    """A lane vector v (1, blk) as the (blk, width) array whose every column
    is v: a transpose, exact."""
    return jnp.transpose(jnp.broadcast_to(v, (width, v.shape[1])))


def _gather(loc: jax.Array, tile: jax.Array) -> jax.Array:
    """Rows `loc` (1, blk) of a VMEM tile (tile_n, w) as (blk, w): a one-hot
    (blk, tile_n) matmul, exact."""
    tile_n = tile.shape[0]
    onehot = jax.lax.broadcasted_iota(jnp.int32, (loc.shape[1], tile_n), 1) == _rows(loc, tile_n)
    return dot01(onehot.astype(jnp.bfloat16), tile)


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
    blk, out_cols = contrib.shape
    weighted = contrib * _rows(vals_ref[...], out_cols)
    seg = jax.lax.broadcasted_iota(jnp.int32, (tile_i, blk), 0) == iloc_ref[...]
    out_ref[...] += dot01(seg.astype(jnp.bfloat16), weighted)


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
