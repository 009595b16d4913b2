"""Compulsory work of one CP-ALS iteration's MTTKRP kernels.

Per output mode m the kernel must read the COO stream once (N int32
coordinates and one float32 value per nonzero), read every input factor
once and write the output once, each (I_n, R) float32 at the true rank R.
Per nonzero the contraction is the value times the Hadamard product of
N-1 factor rows, added into the output row: N*R operations.  Nothing is
counted for padded slots, lanes padded to 128, one-hot gathers, segment
matmuls or tile refetches, so a layout or kernel that wastes less raises
the share and none can read above the chip's peak.
"""
from __future__ import annotations

INDEX_BYTES = 4
VALUE_BYTES = 4


def kernel_work(shape, nnz: int, rank) -> list[dict]:
    """[{"bytes", "flops"}] for each output mode, in mode order."""
    n, r = len(shape), int(rank)
    stream = nnz * (n * INDEX_BYTES + VALUE_BYTES)
    factors = sum(int(s) for s in shape) * r * VALUE_BYTES
    return [{"bytes": stream + factors, "flops": nnz * n * r} for _ in shape]
