"""Compulsory work of one Tucker-HOOI iteration's TTMc kernels.

Per output mode m the kernel must read the COO stream once (N int32
coordinates and one float32 value per nonzero), read every input factor
(I_n, R_n) once and write the unfolding Y_m (I_m, P_m) once, where P_m is
the product of the other modes' ranks, all float32 at the true ranks.  Per
nonzero the contraction scales the narrowest input row by the value, forms
the Kronecker product of the N-1 rows (narrowest first) and adds it into
the output row.  Padded slots, lane padding, one-hot gathers, the spread
matmuls that stand in for the Kronecker product and tile refetches are not
counted.
"""
from __future__ import annotations

import math

INDEX_BYTES = 4
VALUE_BYTES = 4


def _kron_flops(widths) -> int:
    ws = sorted(int(w) for w in widths)
    flops, width = ws[0], ws[0]  # value times the narrowest row
    for w in ws[1:]:
        width *= w
        flops += width
    return flops + width  # + the accumulate


def kernel_work(shape, nnz: int, rank) -> list[dict]:
    """[{"bytes", "flops"}] for each output mode, in mode order."""
    n, ranks = len(shape), [int(r) for r in rank]
    stream = nnz * (n * INDEX_BYTES + VALUE_BYTES)
    out = []
    for m in range(n):
        others = [ranks[k] for k in range(n) if k != m]
        reads = sum(int(shape[k]) * ranks[k] for k in range(n) if k != m)
        write = int(shape[m]) * math.prod(others)
        out.append({
            "bytes": stream + (reads + write) * VALUE_BYTES,
            "flops": nnz * _kron_flops(others),
        })
    return out
