"""spMTTKRP compute patterns (paper Sec. 3, Algorithms 2-5), pure JAX.

Both approaches compute, for each non-zero x at (i0..iN-1) and output mode m:

    out[i_m, :] += x * prod_{n != m} F_n[i_n, :]

They differ only in traversal order — which on TPU becomes *which lowering
XLA picks*:

  * Approach 1 (output-direction, stream sorted by output coordinate):
    `segment_sum` with `indices_are_sorted=True` — a streaming segmented
    reduction, no partial-sum materialization (matches Alg. 3 / Alg. 5).
  * Approach 2 (input-direction, unsorted stream): scatter-add — XLA
    materializes and re-reads accumulator traffic, the moral equivalent of the
    paper's DRAM partial sums (matches Alg. 4).

The hot 3-mode path additionally has a Pallas kernel (kernels/mttkrp_pallas.py)
driven by the BlockPlan layout; this module is the N-mode reference + the
distributed (shard_map) implementation.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "hadamard_rows",
    "mttkrp_approach1",
    "mttkrp_approach2",
    "mttkrp",
    "mttkrp_sharded",
]


def hadamard_rows(indices: jax.Array, values: jax.Array, factors: Sequence[jax.Array], mode: int) -> jax.Array:
    """Per-non-zero Hadamard products: rows of the Khatri-Rao product gathered
    through the tensor's indices.  (nnz, R)."""
    prod = None
    for n, f in enumerate(factors):
        if n == mode:
            continue
        rows = f[indices[:, n]]  # gather: the Cache-Engine access pattern
        prod = rows if prod is None else prod * rows
    assert prod is not None
    return prod * values[:, None].astype(prod.dtype)


@partial(jax.jit, static_argnames=("mode", "out_rows", "sorted_by_mode"))
def mttkrp_approach1(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    out_rows: int,
    sorted_by_mode: bool = True,
) -> jax.Array:
    """Approach 1: output-direction computation over a stream sorted by the
    output mode (Alg. 3).  Lowered as a sorted segmented reduction."""
    contrib = hadamard_rows(indices, values, factors, mode)
    return jax.ops.segment_sum(
        contrib,
        indices[:, mode],
        num_segments=out_rows,
        indices_are_sorted=sorted_by_mode,
    )


@partial(jax.jit, static_argnames=("mode", "out_rows"))
def mttkrp_approach2(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    out_rows: int,
) -> jax.Array:
    """Approach 2: input-direction computation (Alg. 4) — unsorted stream,
    scatter-add accumulation (partial sums materialized by the backend)."""
    contrib = hadamard_rows(indices, values, factors, mode)
    out = jnp.zeros((out_rows, contrib.shape[1]), contrib.dtype)
    return out.at[indices[:, mode]].add(contrib, indices_are_sorted=False, unique_indices=False)


def mttkrp(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    mode: int,
    out_rows: int,
    *,
    method: str = "approach1",
    sorted_by_mode: bool = True,
) -> jax.Array:
    """Dispatcher. `method` in {approach1, approach2}.  The Pallas path is
    dispatched in kernels/ops.py (it needs the host-side BlockPlan)."""
    if method == "approach1":
        return mttkrp_approach1(
            indices, values, factors, mode, out_rows, sorted_by_mode=sorted_by_mode
        )
    if method == "approach2":
        return mttkrp_approach2(indices, values, factors, mode, out_rows)
    raise ValueError(f"unknown method {method!r}")


# ---------------------------------------------------------------------------
# Distributed MTTKRP (shard_map over the non-zero stream)
# ---------------------------------------------------------------------------


def mttkrp_sharded(
    plan,
    mode: int,
    out_rows: int,
    method: str = "approach1",
    *,
    sorted_by_mode: bool = False,
    st=None,
    rank: int | None = None,
    cfg=None,
):
    """Build a shard_map'd MTTKRP from a ``ShardingPlan``: the non-zero
    stream is sharded over the plan's data axes (``plan.stream()``), factor
    matrices replicated, outputs psum-reduced over the same axes.

    This is the production distribution of the paper's kernel: every device
    runs Approach 1 on its local remapped shard; the output factor matrix is
    reduced across the stream shards (one all-reduce of I_out x R — the
    `I_out*R` store term of Table 1, now a collective).  Pass
    ``sorted_by_mode=True`` only when every local shard is sorted by the
    output-mode coordinate (sorting globally then sharding contiguously
    satisfies this — the remap posture); the default assumes an unsorted
    stream, since ``indices_are_sorted`` is a correctness promise to XLA,
    not a hint.

    method="pallas" dispatches the *planned* route instead: the host-side
    ``st`` (SparseTensor) and ``rank`` are required, the stream is
    partitioned into balanced output-tile ranges and each shard gets its own
    device-local BlockPlan layout (kernels/ops.make_sharded_planned_mttkrp).
    The returned callable keeps the (indices, values, factors) signature for
    drop-in use, but the stream arguments are ignored — each shard's
    remapped copy already lives on its device."""
    if method == "pallas":
        if st is None or rank is None:
            raise ValueError(
                "mttkrp_sharded(method='pallas') needs the host-side stream: "
                "pass st=<SparseTensor> and rank=<int> (the BlockPlan "
                "partitioner runs on host-side numpy)"
            )
        from ..kernels.ops import make_sharded_planned_mttkrp

        op = make_sharded_planned_mttkrp(st, mode, rank, dist=plan, cfg=cfg)

        def call_planned(indices, values, factors):
            del indices, values  # per-shard layouts are device-resident
            return op.output(factors, out_rows)

        return call_planned

    axis_names = plan.data_axes()

    def local_fn(indices, values, *factors):
        out = mttkrp(
            indices, values, factors, mode, out_rows,
            method=method, sorted_by_mode=sorted_by_mode,
        )
        return jax.lax.psum(out, axis_names)

    def call(indices, values, factors):
        in_specs = (plan.stream(), plan.stream()) + tuple(
            P(None, None) for _ in factors
        )
        return jax.shard_map(
            local_fn,
            mesh=plan.mesh,
            in_specs=in_specs,
            out_specs=P(None, None),
            check_vma=False,
        )(indices, values, *factors)

    return call
