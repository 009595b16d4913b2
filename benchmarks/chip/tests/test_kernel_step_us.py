"""The `kernel_step_us` reader: kernel device time per iteration over the
program's `kernel.grid_steps{mode=}` gauges, and nothing where the program
records none.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import importlib.util
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent.parent / "src"))


def _reader():
    spec = importlib.util.spec_from_file_location(
        "kernel_step_us", HERE.parent / "metrics" / "kernel_step_us.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_kernel_step_us_reads_the_program_gauges():
    from repro.obs import metrics

    read = _reader()
    r = types.SimpleNamespace(iterations=4, trace=types.SimpleNamespace(
        kernel_s=0.6, kernel_events=12))
    metrics.reset()
    try:
        assert read(r) is None  # a program without the gauges
        metrics.gauge("kernel.grid_steps", mode=0).set(100_000)
        metrics.gauge("kernel.grid_steps", mode=1).set(50_000)
        assert read(r) == pytest.approx(1.0)  # 150 ms per iteration over 150k steps
        assert read(types.SimpleNamespace(iterations=4, trace=None)) is None
    finally:
        metrics.reset()
