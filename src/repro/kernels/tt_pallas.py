"""Blocked sorted-COO TT-core-update Pallas kernel — tensor-train ALS on the
same programmable memory controller as MTTKRP and TTMc.

The TT-ALS loop needs, per output mode m, the right-hand side of the core's
normal equations restricted to X's non-zeros: every nnz z contributes

    value_z * kron(l_z, r_z)           (rl_m * rr_m columns)

to output row i_m, where l_z is the LEFT interface chain
G_0[:, i_0, :] ... G_{m-1}[:, i_{m-1}, :]  (a row vector of width rl_m) and
r_z is the RIGHT interface chain G_{m+1}[:, i_{m+1}, :] ... G_{N-1} (a column
vector of width rr_m, applied to a vector of ones from the right).  That is
TTMc with the full Kronecker chain collapsed to a Kronecker of TWO chained
interfaces — the irregular memory access pattern is IDENTICAL, so the kernel
runs on the same scaffold (kernels/blocked.py) over the same BlockPlan.

Each input factor is a core's interface matrix
W_k = transpose(G_k, (1,0,2)).reshape(I_k, rl_k*rr_k) (row-major — rl slow,
rr fast), lane-padded to rank_padded(rl_k*rr_k).  Gathered rows fold into
the left chain (inputs left of the output mode, ascending) or the right
chain (inputs right of it, descending).  Both chains are (blk, cw) vectors,
cw the lane padding of the widest bond, and every step is an exact 0/1
matmul spread (`dot01`), an elementwise product and an exact 0/1 matmul
reduction — no reshape splits a lane dimension.  `plan.in_modes` is
ascending, so n_left — the number of left-chain inputs — equals the output
mode.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from .blocked import blocked_call, dot01, spread
from .mttkrp_pallas import rank_padded

__all__ = ["ttcore_pallas_call", "tt_out_pair", "tt_out_cols"]


def tt_out_pair(
    in_rank_pairs: Sequence[tuple[int, int]], n_left: int
) -> tuple[int, int]:
    """The output core's interface pair (rl_m, rr_m), recovered from the
    input pairs: rl_m is the last left-chain factor's right bond (1 when the
    output is the first core), rr_m the first right-chain factor's left bond
    (1 when it is the last)."""
    n_in = len(in_rank_pairs)
    rl = in_rank_pairs[n_left - 1][1] if n_left > 0 else 1
    rr = in_rank_pairs[n_left][0] if n_left < n_in else 1
    return (rl, rr)


def tt_out_cols(in_rank_pairs: Sequence[tuple[int, int]], n_left: int) -> int:
    """Number of true output columns: rl_m * rr_m."""
    rl, rr = tt_out_pair(in_rank_pairs, n_left)
    return rl * rr


def _chain_contract(
    in_rank_pairs: tuple[tuple[int, int], ...], n_left: int, pp: int, rows: list
) -> jax.Array:
    """kron(left chain, right chain) of the gathered interface rows, (blk, pp).

    With c = a * rr + b the column of an (rl, rr) interface row:
      left step   l'[:, b] = sum_a l[:, a] W[:, c]  — spread l by c // rr,
                  multiply, reduce by c % rr;
      right step  r'[:, a] = sum_b W[:, c] r[:, b]  — spread r by c % rr,
                  multiply, reduce by c // rr."""
    blk = rows[0].shape[0]
    cw = rank_padded(max(max(p) for p in in_rank_pairs))
    lanes = jax.lax.broadcasted_iota(jnp.int32, (blk, cw), 1)
    unit = (lanes == 0).astype(jnp.bfloat16)  # the width-1 chain start, 0/1
    left = right = unit
    for n in range(n_left):
        rl, rr = in_rank_pairs[n]
        w = rows[n].shape[1]
        prod = dot01(left, spread(cw, w, width=rl * rr, stride=rr, count=rl)) * rows[n]
        left = dot01(prod, spread(w, cw, width=rl * rr, stride=1, count=rr, transpose=True))
    for n in range(len(in_rank_pairs) - 1, n_left - 1, -1):
        rl, rr = in_rank_pairs[n]
        w = rows[n].shape[1]
        prod = rows[n] * dot01(right, spread(cw, w, width=rl * rr, stride=1, count=rr))
        right = dot01(prod, spread(w, cw, width=rl * rr, stride=rr, count=rl, transpose=True))
    rl, rr = tt_out_pair(in_rank_pairs, n_left)
    return (
        dot01(left, spread(cw, pp, width=rl * rr, stride=rr, count=rl))
        * dot01(right, spread(cw, pp, width=rl * rr, stride=1, count=rr))
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tile_i", "in_tiles", "in_rank_pairs", "n_left", "out_rows", "interpret",
    ),
)
def ttcore_pallas_call(
    block_it: jax.Array,  # (nblocks,) int32
    block_in: Sequence[jax.Array],  # N-1 x (nblocks,) int32 input tile ids
    vals: jax.Array,  # (nblocks, 1, blk)
    iloc: jax.Array,  # (nblocks, 1, blk) int32
    in_locs: Sequence[jax.Array],  # N-1 x (nblocks, 1, blk) int32
    factors_pad: Sequence[jax.Array],  # N-1 x (rows_n, rank_padded(rl*rr))
    *,
    tile_i: int,
    in_tiles: tuple[int, ...],  # N-1 input tile sizes
    in_rank_pairs: tuple[tuple[int, int], ...],  # N-1 (rl, rr) bond pairs
    n_left: int,  # inputs left of the output mode (== the output mode)
    out_rows: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (out_rows, rank_padded(rl_m*rr_m)) float32: the mode-m TT-ALS
    right-hand side B_m with columns row-major over (rl_m, rr_m).  Input
    interface matrices in plan.in_modes order (ascending), each lane-padded
    to its own rank_padded(rl_n*rr_n)."""
    in_rank_pairs = tuple((int(a), int(b)) for a, b in in_rank_pairs)
    if len(in_rank_pairs) != len(in_tiles) or not 0 <= n_left <= len(in_tiles):
        raise ValueError(
            f"{len(in_rank_pairs)} bond pairs and n_left={n_left} for "
            f"{len(in_tiles)} input modes"
        )
    pp = rank_padded(tt_out_cols(in_rank_pairs, n_left))
    return blocked_call(
        functools.partial(_chain_contract, in_rank_pairs, n_left, pp),
        block_it, block_in, vals, iloc, in_locs, factors_pad,
        tile_i=tile_i, in_tiles=in_tiles, out_rows=out_rows, out_cols=pp,
        interpret=interpret,
    )
