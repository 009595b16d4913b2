"""The coordinate generator: bit for bit what the linear-index algorithm
gave wherever a tensor's linear index fits int64, and distinct, in bounds
and in order where it does not.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import tensors  # noqa: E402

DELICIOUS = (532924, 17262471, 2480308, 1443)
FLICKR = (319686, 28153045, 1607191, 731)
LBNL = (1605, 4198, 1631, 4209, 868131)


def linear_index_coordinates(shape, nnz, skew, structure_seed):
    """The generator as it stood on one linear index per cell, kept as the
    reference for every shape whose linear index fits int64."""
    shape = tuple(int(s) for s in shape)
    total = int(np.prod(shape, dtype=np.float64))
    if not 0 < nnz <= total // 2:
        raise ValueError(f"nnz {nnz} does not fit a {shape} tensor as a sparse set")
    rng = np.random.default_rng(structure_seed)
    draw = [tensors._zipf_sampler(rng, s, a) for s, a in zip(shape, skew)]
    cells = np.empty((0,), np.int64)
    while cells.size < nnz:
        n = max(1024, int(1.5 * (nnz - cells.size)))
        lin = np.ravel_multi_index(tuple(d(n) for d in draw), shape)
        cells = np.union1d(cells, lin)
    if cells.size > nnz:
        cells = np.sort(rng.choice(cells, size=nnz, replace=False))
    return np.stack(np.unravel_index(cells, shape), axis=1)


def _config(name):
    c = json.loads((HERE / "data" / "configs" / f"{name}.json").read_text())
    return c["shape"], c["nnz"], c["skew"], c["structure_seed"]


@pytest.mark.parametrize("shape, nnz, skew, seed", [
    _config("tiny"),
    _config("tiny4"),
    ((50, 60, 70), 20_000, (1.0, 1.0, 1.0), 3),  # skewed enough for several rounds
    ((12092, 9184, 28818), 30_000, (1.1, 1.1, 1.1), 0),
    ((183, 24, 1140, 1717), 40_000, (0.0, 0.3, 1.0, 1.0), 1),
    ((5000, 7, 300_000, 41), 25_000, (1.2, 0.0, 0.8, 0.5), 2),
    ((1605, 4198, 1631, 4209, 86), 10_000, (1.0, 1.0, 1.0, 1.0, 0.0), 4),
    ((2**20, 2**20, 2**20), 10_000, (0.9, 0.0, 0.9), 5),  # 2**60 cells
], ids=["tiny", "tiny4", "dense3", "nell2", "uber", "mixed4", "five", "wide3"])
def test_equals_linear_index_generator(shape, nnz, skew, seed):
    got = tensors.coordinates(shape, nnz, skew, seed)
    want = linear_index_coordinates(shape, nnz, skew, seed)
    assert got.dtype == want.dtype == np.int64
    assert got.shape == want.shape == (nnz, len(shape))
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("shape, alpha", [
    (DELICIOUS, 1.1),
    (FLICKR, 1.1),
    (LBNL, 1.1),
    ((3_000_000, 3_000_000, 3_000_000, 2), 2.0),  # hot rows: several rounds
], ids=["delicious", "flickr", "lbnl", "hot"])
def test_beyond_int64_is_a_sorted_set(shape, alpha):
    with pytest.raises(ValueError):
        np.ravel_multi_index(tuple(np.zeros((1, len(shape)), np.int64).T), shape)
    assert len(tensors._mode_runs(shape)) == 2
    nnz = 10_000
    x = tensors.coordinates(shape, nnz, [alpha] * len(shape), 0)
    assert x.dtype == np.int64 and x.shape == (nnz, len(shape))
    assert (x >= 0).all() and (x < np.asarray(shape)).all()
    rows = [tuple(r) for r in x.tolist()]
    assert rows == sorted(rows) and len(set(rows)) == nnz
    assert np.array_equal(x, tensors.coordinates(shape, nnz, [alpha] * len(shape), 0))


def test_too_dense_is_refused():
    with pytest.raises(ValueError):
        tensors.coordinates((4, 4, 4), 33, (0.0, 0.0, 0.0), 0)
