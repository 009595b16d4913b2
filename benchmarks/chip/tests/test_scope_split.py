"""Device time per sweep scope and idle time per job phase (scope_split.py),
on hand-made events, on a small trace recorded on a TPU v5e by
tests/record_scoped_trace.py (tests/data/scoped.xplane.pb, with the scope
map beside it) and on the older, unscoped tests/data/small.xplane.pb; and
the `kernel_fetch_gib` reader.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent.parent / "src"))

import scope_split as ss  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Event  # noqa: E402

DATA = HERE / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"
# Two programs of one module name, as CP's first and steady sweeps: most
# instruction names in common, one of them under another scope.
PROGRAMS = [
    {"module": "jit_sweep", "scopes": {
        "kernel.1": "cp.m0.kernel", "fusion.2": "cp.m0.update", "fusion.3": "cp.fit",
        "copy.7": None, "param.0": None}},
    {"module": "jit_sweep", "scopes": {
        "kernel.1": "cp.m0.kernel", "fusion.2": "cp.fit", "fusion.4": "cp.m0.update",
        "copy.7": None, "param.0": None}},
]


def _op(name, s, e):
    return Event(DEV, tr.OPS_LINE, f"%{name} = f32[8]{{0}} fusion()", float(s), float(e - s))


def _mod(name, s, e):
    return Event(DEV, ss.MODULES_LINE, name, float(s), float(e - s))


def _host(name, s, e):
    return Event(HOST, "python", name, float(s), float(e - s))


def test_split_by_hand():
    events = [
        _host(tr.WINDOW, 0, 1000),
        _host("decompose", 50, 900),
        _host("job.init", 50, 80),
        _host("drive", 100, 880),
        _host("sweep", 100, 400),   # first sweep: ends at its fit read-back
        _host("sweep", 400, 600),
        _host("sweep", 600, 850),
        _op("other.9", 20, 40),     # not a sweep program: busy, but no scope
        # fusion.3 ran: only the first program has it
        _op("kernel.1", 150, 300), _op("fusion.2", 300, 320), _op("fusion.3", 320, 330),
        _op("copy.7", 330, 340),
        # fusion.4 ran: the second program, which names fusion.2 otherwise
        _op("kernel.1", 450, 550), _op("fusion.4", 550, 555), _op("fusion.2", 555, 560),
        _op("kernel.1", 700, 800), _op("fusion.4", 800, 805), _op("fusion.2", 805, 810),
    ]
    modules = [_mod("jit_other(5)", 15, 45), _mod("jit_sweep(11)", 140, 345),
               _mod("jit_sweep(22)", 440, 565), _mod("jit_sweep(22)", 690, 815)]
    out = ss.split(events, modules, PROGRAMS)
    assert out.scope_s == pytest.approx({
        "cp.m0.kernel": 350e-9, "cp.m0.update": 30e-9, "cp.fit": 20e-9, ss.UNSCOPED: 10e-9})
    assert out.sweep_s == pytest.approx(410e-9)
    # Idle gaps of the device: 0-20, 40-150, 340-450, 560-700, 810-1000.
    # Iterations run from the first sweep's end (400) to the last's (850);
    # decompose from 50 to 900.
    assert out.idle_s == pytest.approx({
        "iteration": (450 - 400 + 700 - 560 + 850 - 810) * 1e-9,
        "job": (150 - 50 + 400 - 340 + 900 - 850) * 1e-9,
        "outside": (20 + 50 - 40 + 1000 - 900) * 1e-9,
    })
    red = tr.reduce(events, chips=1)
    assert sum(out.idle_s.values()) == pytest.approx(red.window_s - red.busy_s)


def test_split_refuses_to_choose_between_programs():
    """Ops both programs hold, under other scopes: no program is chosen."""
    events = [_host(tr.WINDOW, 0, 100), _op("kernel.1", 10, 20), _op("fusion.2", 20, 30)]
    with pytest.raises(ValueError, match="other scopes"):
        ss.split(events, [_mod("jit_sweep(11)", 5, 35)], PROGRAMS)


def test_recorded_scoped_chip_trace():
    """Two CP jobs of two iterations on a TPU v5e, with the sweep scopes and
    the program's map of its two compiled sweeps beside the trace: each
    sweep execution is matched to its own program (the first iteration's
    and the steady one's), the scopes take all but a few microseconds of the
    sweeps' device time (XLA's own copies carry no metadata), the kernel
    scopes hold the kernel events, and the idle phases add up."""
    path = str(DATA / "scoped.xplane.pb")
    events = tr.load(path)
    programs = json.loads((DATA / "scoped.scopes.json").read_text())
    assert [p["module"] for p in programs] == ["jit_sweep", "jit_sweep"]
    out = ss.split(events, ss.load_modules(path), programs)
    ms = {k: v * 1e3 for k, v in out.scope_s.items()}
    assert ms == pytest.approx({
        "cp.m0.kernel": 4.717628, "cp.m1.kernel": 4.644791, "cp.m2.kernel": 4.580947,
        "cp.m0.update": 0.082436, "cp.m1.update": 0.036254, "cp.m2.update": 0.046855,
        "cp.fit": 0.91445, ss.UNSCOPED: 0.003244}, abs=1e-6)
    assert out.sweep_s * 1e3 == pytest.approx(15.026605, abs=1e-6)
    assert {k: v * 1e3 for k, v in out.idle_s.items()} == pytest.approx(
        {"iteration": 4.350326, "job": 47.054328, "outside": 0.703684}, abs=1e-6)
    red = tr.reduce(events, chips=1)
    assert red.kernel_events == 2 * 2 * 3
    kernels = sum(v for k, v in out.scope_s.items() if k.endswith(".kernel"))
    assert kernels == pytest.approx(red.kernel_s, abs=1e-9)
    assert out.scope_s[ss.UNSCOPED] < 1e-3 * out.sweep_s <= red.busy_s
    assert sum(out.idle_s.values()) == pytest.approx(red.window_s - red.busy_s, abs=1e-9)


def test_idle_phases_on_the_unscoped_chip_trace():
    """tests/data/small.xplane.pb predates the scopes but carries the
    program's spans: two CP jobs of two iterations, so one iteration
    interval per job.  Its idle splits into the three phases and they add
    up to trace_reduce's idle."""
    path = str(DATA / "small.xplane.pb")
    events = tr.load(path)
    out = ss.split(events, ss.load_modules(path), [])
    assert out.scope_s == {} and out.sweep_s == 0.0
    assert out.idle_s == pytest.approx(
        {"iteration": 4.184449e-3, "job": 45.979157e-3, "outside": 0.641826e-3}, abs=1e-9)
    red = tr.reduce(events, chips=1)
    assert sum(out.idle_s.values()) == pytest.approx(red.window_s - red.busy_s, abs=1e-9)


def test_kernel_fetch_gib_reads_the_program_gauges():
    from repro.obs import metrics

    spec = importlib.util.spec_from_file_location(
        "kernel_fetch_gib", HERE.parent / "metrics" / "kernel_fetch_gib.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    metrics.reset()
    try:
        assert reader.read(None) is None  # a program without the gauges
        metrics.gauge("kernel.fetch_bytes", mode=0).set(2**30)
        metrics.gauge("kernel.fetch_bytes", mode=1).set(2**29)
        assert reader.read(None) == 1.5
    finally:
        metrics.reset()
