"""Sparse tensors of a configuration, made from a seed.

A configuration names a FROSTT tensor's mode lengths and nonzero count and
states, under `assumed`, how its coordinates are skewed.  The coordinates
are distinct (a FROSTT tensor is a set) and drawn with a Zipf law per mode,
the hot rows scattered by a random relabelling, as real tensors have a few
very dense rows.  They depend only on the configuration's `structure_seed`:
the tensor is the deployment, and every run of a cell walks the same block
layout and so the same compiled shapes.  The run's `--seed` draws the
values and the order in which the nonzeros reach the program.
"""
from __future__ import annotations

import numpy as np

__all__ = ["coordinates", "generate"]


def _zipf_sampler(rng: np.random.Generator, size: int, alpha: float):
    """Draws of one mode's coordinates: Zipf(alpha) over the ranks, ranks
    relabelled by a fixed permutation; alpha 0 is uniform."""
    if alpha <= 0:
        return lambda n: rng.integers(0, size, n, dtype=np.int64)
    probs = np.arange(1, size + 1, dtype=np.float64) ** (-alpha)
    probs /= probs.sum()
    labels = rng.permutation(size)
    return lambda n: labels[rng.choice(size, size=n, p=probs)]


def coordinates(shape, nnz: int, skew, structure_seed: int) -> np.ndarray:
    """`nnz` distinct coordinates, (nnz, nmodes) int64, in ascending
    linear order.  Draws in rounds until enough distinct cells are hit,
    then keeps a random `nnz` of them."""
    shape = tuple(int(s) for s in shape)
    total = int(np.prod(shape, dtype=np.float64))
    if not 0 < nnz <= total // 2:
        raise ValueError(f"nnz {nnz} does not fit a {shape} tensor as a sparse set")
    rng = np.random.default_rng(structure_seed)
    draw = [_zipf_sampler(rng, s, a) for s, a in zip(shape, skew)]
    cells = np.empty((0,), np.int64)
    while cells.size < nnz:
        n = max(1024, int(1.5 * (nnz - cells.size)))
        lin = np.ravel_multi_index(tuple(d(n) for d in draw), shape)
        cells = np.union1d(cells, lin)
    if cells.size > nnz:
        cells = np.sort(rng.choice(cells, size=nnz, replace=False))
    return np.stack(np.unravel_index(cells, shape), axis=1)


def generate(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray, tuple[int, ...]]:
    """(indices (nnz, N) int32, values (nnz,) float32, shape) of a
    configuration: its fixed coordinates, values from a standard normal
    drawn from `seed`, nonzeros in an order drawn from `seed`."""
    shape = tuple(int(s) for s in config["shape"])
    coords = coordinates(shape, int(config["nnz"]), config["skew"], int(config["structure_seed"]))
    rng = np.random.default_rng(seed % 2**64)
    values = rng.standard_normal(coords.shape[0]).astype(np.float32)
    order = rng.permutation(coords.shape[0])
    return coords[order].astype(np.int32), values, shape
