"""Blocked sorted-COO MTTKRP Pallas kernel — the memory controller in silicon.

The scaffold (stream blocks, factor-tile selection, one-hot gathers, the
Approach-1 accumulator, SMEM chunking) is `kernels/blocked.py`; MTTKRP's own
part is the per-element product: the Hadamard product of the gathered rows
of every input factor, at the shared lane padding R_pad.  The product is
unrolled over the number of input modes (N-1 for an N-mode tensor), so 3-,
4- and 5-mode tensors (paper Table 2) all run on the same generator.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from .blocked import blocked_call

__all__ = ["mttkrp_pallas_call", "pad_factor", "rank_padded"]


def rank_padded(rank: int) -> int:
    return max(128, ((rank + 127) // 128) * 128)


def pad_factor(f: jax.Array, rows: int, rp: int) -> jax.Array:
    """Zero-pad a factor matrix to (rows, rp); padded rows/lanes contribute 0."""
    out = jnp.zeros((rows, rp), f.dtype)
    return out.at[: f.shape[0], : f.shape[1]].set(f)


@functools.partial(
    jax.jit, static_argnames=("tile_i", "in_tiles", "out_rows", "interpret")
)
def mttkrp_pallas_call(
    block_it: jax.Array,  # (nblocks,) int32
    block_in: Sequence[jax.Array],  # N-1 x (nblocks,) int32 input tile ids
    vals: jax.Array,  # (nblocks, 1, blk)
    iloc: jax.Array,  # (nblocks, 1, blk) int32
    in_locs: Sequence[jax.Array],  # N-1 x (nblocks, 1, blk) int32
    factors_pad: Sequence[jax.Array],  # N-1 x (rows_n, rp), plan.in_modes order
    *,
    tile_i: int,
    in_tiles: tuple[int, ...],  # N-1 input tile sizes
    out_rows: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (out_rows, rp) float32; `interpret=None` decides from the
    platform."""
    return blocked_call(
        math.prod, block_it, block_in, vals, iloc, in_locs, factors_pad,
        tile_i=tile_i, in_tiles=in_tiles, out_rows=out_rows,
        out_cols=factors_pad[0].shape[1], interpret=interpret,
    )
