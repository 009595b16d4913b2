"""The control of the comparison, at a size a test run holds.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

The control is the plain reference put in the program's place and computed
one precision step below the configurations' float32 with exact products:
`Precision.HIGH`, three bf16 passes per product.  On the chip it is read by
readings.py at each cell's own size; here, at the tiny sizes under
tests/data, it must fail one of each cell's numbers while the program's
own last iteration passes every one.  (On the CPU, matmuls run in float32
whatever the precision setting, so only the elementwise products are
rounded here; the control reads higher on the chip.)
"""
import json

import pytest

import tiny
from check import control_gaps, judge, program_gaps, state_arrays


def _last_iteration(cell, seed):
    import tensors
    from repro.api import decompose
    from repro.core.coo import SparseTensor

    config, traffic = tiny.CELLS[cell]
    config = json.loads((tiny.DATA / "configs" / f"{config}.json").read_text())
    traffic = json.loads((tiny.DATA / "traffic" / f"{traffic}.json").read_text())
    rank = traffic["rank"] if isinstance(traffic["rank"], int) else tuple(traffic["rank"])
    idx, vals, shape = tensors.generate(config, seed)
    st = SparseTensor(idx, vals, shape)
    ws = tiny.run.load_callable(traffic["workspace"])(st, rank)
    job = dict(format=traffic["format"], method="pallas", tol=None, seed=seed, **traffic["options"])
    k = traffic["iters_per_job"]
    done = decompose(st, rank, planned=ws, iters=k, **job)
    before = state_arrays(decompose(st, rank, planned=ws, iters=k - 1, **job))
    return traffic["format"], idx, vals, before, state_arrays(done), done.fit_history[-1]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell", list(tiny.CELLS))
def test_control_fails_and_program_passes(cell, seed):
    limits = json.loads((tiny.DATA / "limits" / f"{cell}.json").read_text())["limits"]
    fmt, idx, vals, before, after, fit = _last_iteration(cell, seed)
    ok, checks = judge(program_gaps(fmt, idx, vals, before, after, fit), limits)
    assert ok, checks
    ok, checks = judge(control_gaps(fmt, idx, vals, before), limits)
    assert not ok, checks
