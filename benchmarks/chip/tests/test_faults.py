"""A run with the timed path broken underneath must come out not correct.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Each fault is planted in the program's workspace as the harness builds it
(the chip check is skipped, the cells are the tiny ones under tests/data):
  * stale: every sweep returns the factors it was given, unchanged;
  * half: the kernels see half of the nonzeros (every other block's values
    zeroed in every mode's layout);
  * fit: the fit each sweep reports is off by 1e-3;
  * entry: the largest entry of the last mode's new factor is 1% off.
The cells run on one chip, so there is no exchange between chips to leave
out.
"""
import jax.numpy as jnp
import pytest

import tiny


def _wrap_sweep(ws, change):
    call = ws._sweep_call

    def faulty(facs, *args, it):
        return change(facs, *call(facs, *args, it=it))

    ws._sweep_call = faulty


def stale(ws):
    _wrap_sweep(ws, lambda facs, new, aux, fit: (facs, aux, fit))


def half(ws):
    for op in ws.ops.values():
        block_it, block_in, vals, iloc, in_locs = op.layout
        op.layout = (block_it, block_in, vals.at[1::2].set(0.0), iloc, in_locs)


def fit(ws):
    _wrap_sweep(ws, lambda facs, new, aux, f: (new, aux, f + 1e-3))


def entry(ws):
    def change(facs, new, aux, f):
        last = new[-1]
        at = jnp.unravel_index(jnp.argmax(jnp.abs(last)), last.shape)
        return (*new[:-1], last.at[at].multiply(1.01)), aux, f

    _wrap_sweep(ws, change)


@pytest.fixture
def plant(monkeypatch):
    def plant_fault(fault):
        real = tiny.run.load_callable

        def builder(spec):
            build = real(spec)
            if not spec.startswith("repro."):
                return build
            return lambda *a, **k: (lambda ws: (fault(ws), ws)[1])(build(*a, **k))

        monkeypatch.setattr(tiny.run, "load_callable", builder)

    return plant_fault


@pytest.mark.parametrize("cell", list(tiny.CELLS))
def test_sound_run_is_correct(cell):
    res = tiny.run_cell(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", [stale, half, fit, entry], ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", ["tiny.cp", "tiny.tt", "tiny.tucker"])
def test_fault_is_not_correct(plant, cell, fault):
    plant(fault)
    res = tiny.run_cell(cell)
    assert not res["correct"], res["checks"]
