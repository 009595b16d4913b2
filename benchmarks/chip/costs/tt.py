"""Compulsory work of one TT-ALS iteration's TT-core kernels.

Cores G_k are (rl_k, I_k, rr_k) with rl_0 = rr_{N-1} = 1 and the traffic's
N-1 interior ranks between.  Per output mode m the kernel must read the
COO stream once (N int32 coordinates and one float32 value per nonzero),
read every other core once and write B_m (I_m, rl_m * rr_m) once, all
float32 at the true ranks.  Per nonzero it chains the left cores' slices
into an rl_m vector and the right cores' into an rr_m vector (a
vector-matrix product per core after the first), scales the narrower by the
value, forms their Kronecker product and adds it into the output row.
Padded slots, lane padding, one-hot gathers, spread matmuls and tile
refetches are not counted.
"""
from __future__ import annotations

INDEX_BYTES = 4
VALUE_BYTES = 4


def bond_pairs(rank, nmodes: int) -> list[tuple[int, int]]:
    bounds = [1] + [int(r) for r in rank] + [1]
    return [(bounds[k], bounds[k + 1]) for k in range(nmodes)]


def _chain_flops(pairs) -> int:
    """Vector-matrix products along a chain that starts at a boundary core,
    whose slice is already the vector."""
    return sum(2 * rl * rr - min(rl, rr) for rl, rr in pairs[1:])


def kernel_work(shape, nnz: int, rank) -> list[dict]:
    """[{"bytes", "flops"}] for each output mode, in mode order."""
    n = len(shape)
    pairs = bond_pairs(rank, n)
    stream = nnz * (n * INDEX_BYTES + VALUE_BYTES)
    out = []
    for m in range(n):
        rl, rr = pairs[m]
        reads = sum(int(shape[k]) * a * b for k, (a, b) in enumerate(pairs) if k != m)
        write = int(shape[m]) * rl * rr
        per_nnz = (
            _chain_flops(pairs[:m])
            + _chain_flops(pairs[m + 1:][::-1])
            + min(rl, rr)
            + (rl * rr if min(rl, rr) > 1 else 0)
            + rl * rr
        )
        out.append({"bytes": stream + (reads + write) * VALUE_BYTES, "flops": nnz * per_nnz})
    return out
