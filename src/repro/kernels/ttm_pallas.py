"""Blocked sorted-COO TTM-chain (TTMc) Pallas kernel — sparse Tucker on the
same programmable memory controller as MTTKRP.

The Tucker HOOI loop needs, per output mode n,

    Y_(n) = X_(n) (U^(m_{N-2}) (x) ... (x) U^(m_1)),   m_* = modes != n,

restricted to X's non-zeros: every nnz z contributes
value_z * kron(U^(m_1)[i_{m_1}, :], ..., U^(m_{N-2})[i_{m_{N-2}}, :]) to output
row i_n.  That is MTTKRP with the per-element Hadamard product replaced by a
Kronecker (outer) product of the gathered factor rows — the irregular memory
access pattern is IDENTICAL, so the kernel runs on the same scaffold
(kernels/blocked.py) over the same BlockPlan layout.

Each input factor keeps its OWN rank R_m (lane-padded to rank_padded(R_m));
the output carries P = prod(R_m) columns (lane-padded to cols_padded(P)).
The Kronecker product is built without reshapes: each input's gathered rows
are spread into the output column layout by an exact 0/1 matmul (`dot01`;
column c takes row entry (c // stride_m) % R_m, stride_m the product of the
later ranks), and the spread rows are multiplied elementwise.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax

from .blocked import blocked_call, dot01, spread
from .mttkrp_pallas import rank_padded

__all__ = ["ttmc_pallas_call", "cols_padded", "kron_cols"]


def cols_padded(ncols: int) -> int:
    """Lane padding for the TTMc output: P = prod(in_ranks) columns padded to
    the 128-lane boundary (same rule as rank_padded — shared on purpose, the
    output tile is a core-tensor slice, not a factor)."""
    return rank_padded(ncols)


def kron_cols(in_ranks: Sequence[int]) -> int:
    """Number of true output columns: P = prod of the input-factor ranks."""
    return math.prod(int(r) for r in in_ranks)


def _kron_contract(in_ranks: tuple[int, ...], pp: int, rows: list) -> jax.Array:
    """kron of the gathered rows (row-major over the inputs), (blk, pp)."""
    p = kron_cols(in_ranks)
    contrib = None
    for n, (r, x) in enumerate(zip(in_ranks, rows)):
        stride = kron_cols(in_ranks[n + 1 :])
        e = spread(x.shape[1], pp, width=p, stride=stride, count=r)
        term = dot01(x, e)
        contrib = term if contrib is None else contrib * term
    return contrib


@functools.partial(
    jax.jit,
    static_argnames=("tile_i", "in_tiles", "in_ranks", "out_rows", "interpret"),
)
def ttmc_pallas_call(
    block_it: jax.Array,  # (nblocks,) int32
    block_in: Sequence[jax.Array],  # N-1 x (nblocks,) int32 input tile ids
    vals: jax.Array,  # (nblocks, 1, blk)
    iloc: jax.Array,  # (nblocks, 1, blk) int32
    in_locs: Sequence[jax.Array],  # N-1 x (nblocks, 1, blk) int32
    factors_pad: Sequence[jax.Array],  # N-1 x (rows_n, Rp_n), plan.in_modes order
    *,
    tile_i: int,
    in_tiles: tuple[int, ...],  # N-1 input tile sizes
    in_ranks: tuple[int, ...],  # N-1 true input-factor ranks
    out_rows: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (out_rows, cols_padded(prod(in_ranks))) float32: the mode-n
    TTMc unfolding with row-major column order over plan.in_modes."""
    in_ranks = tuple(int(r) for r in in_ranks)
    pp = cols_padded(kron_cols(in_ranks))
    return blocked_call(
        functools.partial(_kron_contract, in_ranks, pp),
        block_it, block_in, vals, iloc, in_locs, factors_pad,
        tile_i=tile_i, in_tiles=in_tiles, out_rows=out_rows, out_cols=pp,
        interpret=interpret,
    )
