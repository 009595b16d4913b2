"""The trace reduction, on hand-made events and on a small trace recorded on
a TPU v5e by tests/record_trace.py (tests/data/small.xplane.pb).

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import trace_reduce as tr  # noqa: E402
from trace_reduce import Event  # noqa: E402

FIXTURE = HERE / "data" / "small.xplane.pb"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _dev(name, s, e, text=""):
    return Event(DEV, tr.OPS_LINE, name, float(s), float(e - s), text)


def _host(name, s, e):
    return Event(HOST, "python", name, float(s), float(e - s))


def test_union_kernels_and_gaps_by_hand():
    events = [
        _host(tr.WINDOW, 100, 1100),
        _host("bench.job", 100, 600),
        _host("sweep", 150, 500),
        _host("bench.job", 600, 1100),
        _dev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 50, 200),  # clipped: 100..200
        _dev("custom-call.3", 180, 400,
             'custom-call(), custom_call_target="tpu_custom_call"'),  # overlaps fusion.1
        # A fusion reading the kernel's output names the kernel: not kernel time.
        _dev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %mttkrp_pallas_call.3)", 450, 500),
        _dev("copy.2", 700, 1000),
        _dev("late", 1200, 1300),  # outside the window
    ]
    red = tr.reduce(events)
    assert abs(red.window_s - 1000e-9) < 1e-15
    # Busy: 100..400, 450..500, 700..1000 -> 300 + 50 + 300.
    assert abs(red.busy_s - 650e-9) < 1e-15
    assert red.kernel_events == 1 and abs(red.kernel_s - 220e-9) < 1e-15
    assert [n for n, _ in red.device_ops] == ["copy", "custom-call", "fusion"]
    assert abs(dict(red.device_ops)["fusion"] - 150e-9) < 1e-15
    # Gaps: 400..450 (in sweep), 500..700 (mid 600: the second job starts),
    # 1000..1100 (second job); longest first.
    assert [n for n, _ in red.idle_gaps] == ["bench.job", "bench.job", "sweep"]
    assert np.allclose([t for _, t in red.idle_gaps], [200e-9, 100e-9, 50e-9])


def test_recorded_chip_trace():
    """What the reduction reads from a real TPU trace, against a direct
    count over the same events."""
    events = tr.load(str(FIXTURE))
    red = tr.reduce(events)
    window = next(e for e in events if e.name == tr.WINDOW)
    ops = [e for e in events if e.plane.startswith("/device:")
           and e.start_ns >= window.start_ns and e.end_ns <= window.end_ns]
    assert ops, "the recorded trace holds device ops inside the window"
    assert red.devices == 1
    assert abs(red.window_s - window.dur_ns * 1e-9) < 1e-12
    # Busy time by brute force on a 1 ns grid.
    t0 = int(window.start_ns)
    grid = np.zeros(int(window.dur_ns) + 1, bool)
    for e in ops:
        grid[int(e.start_ns) - t0:int(e.end_ns) - t0] = True
    assert abs(red.busy_s - grid.sum() * 1e-9) < 1e-9 * len(ops) + 1e-6 * red.busy_s
    assert 0 < red.kernel_s <= red.busy_s <= red.window_s
    # Two jobs of two CP iterations on a 3-mode tensor: one kernel call per
    # mode per iteration, and only those; the fusions that read a kernel's
    # output (their HLO names the kernel) are not kernel time.
    assert red.kernel_events == 2 * 2 * 3
    assert red.device_ops[0][0] == "mttkrp_pallas_call"
    assert abs(red.kernel_s - dict(red.device_ops)["mttkrp_pallas_call"]) < 1e-12
    readers = [e for e in ops if "mttkrp_pallas_call" in e.name and not tr._is_kernel(e)]
    assert readers and all(tr.op_name(e.name) == "fusion" for e in readers)
    assert red.idle_gaps
