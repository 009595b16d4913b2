"""Pure-jnp oracles for the MTTKRP, TTM-chain (TTMc) and TT-core kernels.

Independent references per kernel family:
  * `mttkrp_ref`        — gather -> Hadamard -> segment_sum (mirrors Alg. 2).
  * `mttkrp_ref_dense`  — densify + einsum; O(I*J*K*R), tiny shapes only, used
                          to cross-check the sparse reference itself.
  * `ttmc_ref`          — gather -> Kronecker chain -> segment_sum: the sparse
                          TTMc unfolding Y_(n) = X_(n) (kron of input factors)
                          that drives the Tucker HOOI loop.
  * `ttmc_ref_dense`    — densify + einsum cross-check, any order >= 3.
  * `ttcore_ref`        — gather -> left/right interface chains -> Kronecker
                          of two -> segment_sum: the TT-ALS right-hand side
                          B_m that drives the tensor-train loop.
  * `ttcore_ref_dense`  — densify + einsum cross-check, any order >= 3.
Each family also has a `*_plan_ref` oracle operating on the kernel's own
BlockPlan layout (including padded rows).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "mttkrp_ref",
    "mttkrp_ref_dense",
    "mttkrp_plan_ref",
    "ttmc_ref",
    "ttmc_ref_dense",
    "ttmc_plan_ref",
    "ttcore_ref",
    "ttcore_ref_dense",
    "ttcore_plan_ref",
]


def mttkrp_ref(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    out_rows: int,
) -> jax.Array:
    prod = None
    for n, f in enumerate(factors):
        if n == mode:
            continue
        rows = f[indices[:, n]]
        prod = rows if prod is None else prod * rows
    contrib = prod * values[:, None].astype(prod.dtype)
    return jax.ops.segment_sum(contrib, indices[:, mode], num_segments=out_rows)


def mttkrp_ref_dense(
    indices: np.ndarray,
    values: np.ndarray,
    factors: Sequence[np.ndarray],
    mode: int,
    out_rows: int,
) -> np.ndarray:
    """Densify-and-einsum cross-check (3-mode, duplicate-accumulating)."""
    assert len(factors) == 3
    shape = tuple(int(f.shape[0]) for f in factors)
    dense = np.zeros(shape, np.float64)
    np.add.at(dense, tuple(indices[:, m] for m in range(3)), values.astype(np.float64))
    ins = [n for n in range(3) if n != mode]
    letters = "ijk"
    spec = f"ijk,{letters[ins[0]]}r,{letters[ins[1]]}r->{letters[mode]}r"
    out = np.einsum(spec, dense, factors[ins[0]].astype(np.float64), factors[ins[1]].astype(np.float64))
    return out[:out_rows].astype(np.float32)


def ttmc_ref(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    out_rows: int,
) -> jax.Array:
    """Sparse TTM-chain: Y[i_n, :] += v * kron(rows of every factor != mode),
    columns in row-major order over ascending input-mode index.  `factors`
    holds all N factor matrices; the mode-th is ignored.  Returns
    (out_rows, prod of input ranks), at f32 or the inputs' wider type."""
    nnz = values.shape[0]
    dt = jnp.promote_types(values.dtype, jnp.float32)
    contrib = values[:, None].astype(dt)
    for n, f in enumerate(factors):
        if n == mode:
            continue
        rows = f[indices[:, n]].astype(dt)  # (nnz, R_n)
        contrib = (contrib[:, :, None] * rows[:, None, :]).reshape(nnz, -1)
    return jax.ops.segment_sum(contrib, indices[:, mode], num_segments=out_rows)


def ttmc_ref_dense(
    indices: np.ndarray,
    values: np.ndarray,
    factors: Sequence[np.ndarray],
    mode: int,
    out_rows: int,
) -> np.ndarray:
    """Densify-and-einsum cross-check for any order >= 3 (duplicate-
    accumulating, float64 internally): contracts every mode but `mode` with
    its factor and flattens the rank axes row-major."""
    nmodes = len(factors)
    assert nmodes <= 5, "dense oracle is for tiny cross-check shapes only"
    shape = tuple(int(f.shape[0]) for f in factors)
    dense = np.zeros(shape, np.float64)
    np.add.at(dense, tuple(indices[:, m] for m in range(nmodes)), values.astype(np.float64))
    ins = [n for n in range(nmodes) if n != mode]
    letters, ranks = "abcde"[:nmodes], "vwxyz"
    spec = (
        letters
        + ","
        + ",".join(letters[n] + ranks[k] for k, n in enumerate(ins))
        + "->"
        + letters[mode]
        + ranks[: len(ins)]
    )
    out = np.einsum(spec, dense, *[factors[n].astype(np.float64) for n in ins])
    return out.reshape(shape[mode], -1)[:out_rows].astype(np.float32)


def ttmc_plan_ref(
    plan, factors_padded: Sequence[jax.Array], in_ranks: Sequence[int]
) -> jax.Array:
    """Oracle on the kernel's BlockPlan layout: exactly what the Pallas TTMc
    kernel should produce, including padded rows (true columns only — the
    caller compares against out[:, :prod(in_ranks)]).  One lane-padded factor
    per input mode, in plan.in_modes order."""
    blk = plan.blk
    vals = jnp.asarray(plan.vals)
    gi = jnp.repeat(jnp.asarray(plan.block_it), blk) * plan.tile_i + jnp.asarray(plan.iloc)
    contrib = vals[:, None]
    for f_pad, tids, loc, tile, r in zip(
        factors_padded, plan.block_in, plan.in_locs, plan.in_tiles, in_ranks
    ):
        g = jnp.repeat(jnp.asarray(tids), blk) * tile + jnp.asarray(loc)
        rows = f_pad[g][:, :r]
        contrib = (contrib[:, :, None] * rows[:, None, :]).reshape(vals.shape[0], -1)
    return jax.ops.segment_sum(contrib, gi, num_segments=plan.out_rows)


def ttcore_ref(
    indices: jax.Array,
    values: jax.Array,
    cores: Sequence[jax.Array],
    mode: int,
    out_rows: int,
) -> jax.Array:
    """Sparse TT-ALS right-hand side: B[i_m, :] += v * kron(l, r), where l is
    the left interface chain over cores < mode and r the right chain over
    cores > mode, columns row-major over (rl_m, rr_m).  `cores` holds all N
    TT cores, shape (rl_k, I_k, rr_k); the mode-th is ignored.  Returns
    (out_rows, rl_m * rr_m), at f32 or the inputs' wider type."""
    nnz = values.shape[0]
    dt = jnp.promote_types(values.dtype, jnp.float32)
    left = jnp.ones((nnz, 1), dt)
    for k in range(mode):
        rows = jnp.transpose(cores[k], (1, 0, 2))[indices[:, k]]  # (nnz, rl, rr)
        left = jnp.einsum("za,zab->zb", left, rows.astype(dt))
    right = jnp.ones((nnz, 1), dt)
    for k in range(len(cores) - 1, mode, -1):
        rows = jnp.transpose(cores[k], (1, 0, 2))[indices[:, k]]
        right = jnp.einsum("zab,zb->za", rows.astype(dt), right)
    contrib = values[:, None].astype(dt) * (
        left[:, :, None] * right[:, None, :]
    ).reshape(nnz, -1)
    return jax.ops.segment_sum(contrib, indices[:, mode], num_segments=out_rows)


def ttcore_ref_dense(
    indices: np.ndarray,
    values: np.ndarray,
    cores: Sequence[np.ndarray],
    mode: int,
    out_rows: int,
) -> np.ndarray:
    """Densify-and-einsum cross-check for any order >= 3 (duplicate-
    accumulating, float64 internally): contracts the dense tensor with the
    left interface (modes < mode folded into an rl_m-wide matrix) and the
    right interface (modes > mode into rr_m wide), flattening (rl, rr)
    row-major."""
    nmodes = len(cores)
    assert nmodes <= 5, "dense oracle is for tiny cross-check shapes only"
    shape = tuple(int(c.shape[1]) for c in cores)
    dense = np.zeros(shape, np.float64)
    np.add.at(dense, tuple(indices[:, m] for m in range(nmodes)), values.astype(np.float64))
    # Left interface: rows of kron-chained left cores, (prod(shape[:mode]), rl_m).
    left = np.ones((1, 1), np.float64)
    for k in range(mode):
        left = np.einsum("pa,aib->pib", left, cores[k].astype(np.float64))
        left = left.reshape(-1, cores[k].shape[2])
    # Right interface: columns of kron-chained right cores, (rr_m, prod(shape[mode+1:])).
    right = np.ones((1, 1), np.float64)
    for k in range(nmodes - 1, mode, -1):
        right = np.einsum("aib,bq->aiq", cores[k].astype(np.float64), right)
        right = right.reshape(cores[k].shape[0], -1)
    d3 = dense.reshape(left.shape[0], shape[mode], right.shape[1])
    out = np.einsum("piq,pa,bq->iab", d3, left, right)
    return out.reshape(shape[mode], -1)[:out_rows].astype(np.float32)


def ttcore_plan_ref(
    plan,
    factors_padded: Sequence[jax.Array],
    in_rank_pairs: Sequence[tuple[int, int]],
    n_left: int,
) -> jax.Array:
    """Oracle on the kernel's BlockPlan layout: exactly what the Pallas
    TT-core kernel should produce, including padded rows (true columns only —
    the caller compares against out[:, :rl_m*rr_m]).  One lane-padded
    interface matrix per input mode, in plan.in_modes order."""
    blk = plan.blk
    vals = jnp.asarray(plan.vals)
    nnz = vals.shape[0]
    gi = jnp.repeat(jnp.asarray(plan.block_it), blk) * plan.tile_i + jnp.asarray(plan.iloc)
    rows3 = []
    for f_pad, tids, loc, tile, (rl, rr) in zip(
        factors_padded, plan.block_in, plan.in_locs, plan.in_tiles, in_rank_pairs
    ):
        g = jnp.repeat(jnp.asarray(tids), blk) * tile + jnp.asarray(loc)
        rows3.append(f_pad[g][:, : rl * rr].reshape(nnz, rl, rr))
    left = jnp.ones((nnz, 1), jnp.float32)
    for n in range(n_left):
        left = jnp.einsum("za,zab->zb", left, rows3[n])
    right = jnp.ones((nnz, 1), jnp.float32)
    for n in range(len(rows3) - 1, n_left - 1, -1):
        right = jnp.einsum("zab,zb->za", rows3[n], right)
    contrib = vals[:, None] * (left[:, :, None] * right[:, None, :]).reshape(nnz, -1)
    return jax.ops.segment_sum(contrib, gi, num_segments=plan.out_rows)


def mttkrp_plan_ref(plan, factors_padded: Sequence[jax.Array], rank_padded: int) -> jax.Array:
    """Oracle operating on the *kernel's* input layout (BlockPlan): computes
    exactly what the Pallas kernel should produce, including padded rows.
    N-mode: one padded factor per input mode, in plan.in_modes order.
    Returns (out_rows_padded, rank_padded)."""
    blk = plan.blk
    vals = jnp.asarray(plan.vals)
    gi = jnp.repeat(jnp.asarray(plan.block_it), blk) * plan.tile_i + jnp.asarray(plan.iloc)
    contrib = vals[:, None]
    for f_pad, tids, loc, tile in zip(
        factors_padded, plan.block_in, plan.in_locs, plan.in_tiles
    ):
        g = jnp.repeat(jnp.asarray(tids), blk) * tile + jnp.asarray(loc)
        contrib = contrib * f_pad[g]
    return jax.ops.segment_sum(contrib, gi, num_segments=plan.out_rows)
