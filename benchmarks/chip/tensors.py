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

import math

import numpy as np

__all__ = ["coordinates", "generate"]

INDEX_MAX = int(np.iinfo(np.int64).max)
EXACT_MAX = 2**53  # float64 holds every integer up to here exactly


def _zipf_sampler(rng: np.random.Generator, size: int, alpha: float):
    """Draws of one mode's coordinates: Zipf(alpha) over the ranks, ranks
    relabelled by a fixed permutation; alpha 0 is uniform."""
    if alpha <= 0:
        return lambda n: rng.integers(0, size, n, dtype=np.int64)
    probs = np.arange(1, size + 1, dtype=np.float64) ** (-alpha)
    probs /= probs.sum()
    labels = rng.permutation(size)
    return lambda n: labels[rng.choice(size, size=n, p=probs)]


def _mode_runs(shape: tuple[int, ...]) -> list[tuple[int, int]]:
    """The modes as runs [a, b) whose mixed-radix indices key a cell: one run
    where the whole tensor's linear index fits int64; else a leading and a
    trailing run, each with at most 2**53 cells, so that a pair of keys
    sorts exactly as a complex128 (`_distinct`)."""
    if math.prod(shape) <= INDEX_MAX:
        return [(0, len(shape))]
    cut = max(m for m in range(1, len(shape)) if math.prod(shape[:m]) <= EXACT_MAX)
    if math.prod(shape[cut:]) > EXACT_MAX:
        raise ValueError(f"a {shape} tensor's cells need more than two keys")
    return [(0, cut), (cut, len(shape))]


def _distinct(keys: list[np.ndarray]) -> list[np.ndarray]:
    """The distinct rows of one or two key columns, in lexicographic order.
    Two keys below 2**53 are exact in float64, and numpy sorts complex
    numbers by real part, then imaginary part."""
    if len(keys) == 1:
        return [np.unique(keys[0])]
    pairs = np.unique(keys[0].astype(np.float64) + 1j * keys[1].astype(np.float64))
    return [pairs.real.astype(np.int64), pairs.imag.astype(np.int64)]


def coordinates(shape, nnz: int, skew, structure_seed: int) -> np.ndarray:
    """`nnz` distinct coordinates, (nnz, nmodes) int64, in ascending
    lexicographic (C-order linear) order.  Draws in rounds until enough
    distinct cells are hit, then keeps a random `nnz` of them.  A cell is
    keyed by the mixed-radix index of each run of modes (`_mode_runs`),
    so no key overflows where the tensor's linear index would."""
    shape = tuple(int(s) for s in shape)
    total = math.prod(shape)
    if not 0 < nnz <= total // 2:
        raise ValueError(f"nnz {nnz} does not fit a {shape} tensor as a sparse set")
    rng = np.random.default_rng(structure_seed)
    draw = [_zipf_sampler(rng, s, a) for s, a in zip(shape, skew)]
    runs = _mode_runs(shape)
    cells = [np.empty((0,), np.int64) for _ in runs]
    while cells[0].size < nnz:
        n = max(1024, int(1.5 * (nnz - cells[0].size)))
        coords = [d(n) for d in draw]
        keys = [np.ravel_multi_index(tuple(coords[a:b]), shape[a:b]) for a, b in runs]
        cells = _distinct([np.concatenate([c, k]) for c, k in zip(cells, keys)])
    if cells[0].size > nnz:
        keep = np.sort(rng.choice(cells[0].size, size=nnz, replace=False))
        cells = [c[keep] for c in cells]
    return np.concatenate([np.stack(np.unravel_index(c, shape[a:b]), axis=1)
                           for c, (a, b) in zip(cells, runs)], axis=1)


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
