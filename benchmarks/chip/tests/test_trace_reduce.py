"""The trace reduction, on hand-made events and on a small trace recorded on
a TPU v5e by tests/record_trace.py (tests/data/small.xplane.pb).

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

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
    red = tr.reduce(events, chips=1)
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
    red = tr.reduce(events, chips=1)
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


ONE_CHIP = json.loads((HERE / "data" / "one_chip.json").read_text())


@pytest.mark.parametrize("trace", sorted(ONE_CHIP))
def test_one_chip_reduction_is_unchanged(trace):
    """On one chip, per-chip arithmetic reads the recorded traces bit for
    bit as the device-averaged one did (pinned in data/one_chip.json)."""
    red = tr.reduce(tr.load(str(HERE / "data" / trace)), chips=1)
    for key, value in ONE_CHIP[trace].items():
        assert getattr(red, key) == value, key
    assert red.devices == 1 and red.collective_s == 0.0


def test_idle_chip_counts_as_idle():
    """Busy, kernel and op times are per chip of the cell: a chip that ran
    nothing halves them on two chips, and is idle the whole window."""
    events = [
        _host(tr.WINDOW, 0, 1000),
        _dev("custom-call.1", 100, 500, 'custom_call_target="tpu_custom_call"'),
        _dev("copy.2", 600, 700),
    ]
    one, two = tr.reduce(events, chips=1), tr.reduce(events, chips=2)
    assert one.devices == two.devices == 1
    assert abs(one.busy_s - 500e-9) < 1e-15 and abs(two.busy_s - 250e-9) < 1e-15
    assert abs(two.kernel_s - 200e-9) < 1e-15
    assert dict(two.device_ops) == pytest.approx({"custom-call": 200e-9, "copy": 50e-9})
    assert two.idle_gaps[0][1] == pytest.approx(1000e-9)
    assert sorted(t for _, t in one.idle_gaps) == sorted(t for _, t in two.idle_gaps[1:])

    other = Event("/device:TPU:1", tr.OPS_LINE, "copy.3", 0.0, 1000.0)
    both = tr.reduce(events + [other], chips=2)
    assert both.devices == 2
    assert abs(both.busy_s - 750e-9) < 1e-15
    assert dict(both.device_ops)["copy"] == pytest.approx((100 + 1000) / 2 * 1e-9)


def test_collective_time_is_collective_ops_only():
    """`collective_s` sums, per chip, the ops whose HLO name is a
    collective's, either half of an async pair included, and nothing that
    only reads one."""
    coll = ["all-reduce.1", "%all-gather-start.2 = f32[8]{0} all-gather-start(f32[2]{0} %p)",
            "all-gather-done.2", "reduce-scatter", "all-to-all.7", "collective-permute-done.1",
            "all-reduce-start", "all-reduce-done.4",
            "%psum.3 = f32[1792,128]{1,0:T(8,128)} all-reduce(f32[1792,128]{1,0:T(8,128)} %fusion.2),"
            " channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add.1",
            "%psum.4 = (f32[16]{0:T(128)}, f32[]{:T(128)}) all-reduce(f32[16]{0} %a, f32[] %b)"]
    other = ["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1)", "copy-start.1",
             "all-reduce-scatter-fusion.2", "custom-call.5",
             "%copy-done.1 = f32[8]{0} copy-done((f32[8]{0}, u32[]) %all-gather-start.2)",
             "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %psum.3), kind=kLoop, calls=%all-reduce.9"]
    events = [_host(tr.WINDOW, 0, 10_000)]
    for k, name in enumerate(coll + other):
        plane = f"/device:TPU:{k % 2}"
        events.append(Event(plane, tr.OPS_LINE, name, 100.0 * k, 10.0 * (k + 1)))
    red = tr.reduce(events, chips=2)
    want = sum(10.0 * (k + 1) for k in range(len(coll))) / 2
    assert red.collective_s == pytest.approx(want * 1e-9, rel=1e-12)
    assert [tr.is_collective(e) for e in events[1:]] == [True] * len(coll) + [False] * len(other)


@pytest.mark.parametrize("name, text, want", [
    ("psum.3", "psum.3 %psum.3 = f32[1792,128]{1,0} all-reduce(f32[1792,128]{1,0} %fusion.2),"
     " channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add.1", True),
    ("all-gather-start.2", "%all-gather-start.2 = f32[8]{0} all-gather-start(f32[2]{0} %p)", True),
    ("fusion.3", "fusion.3 %fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), calls=%c", False),
    ("copy-done.1", "%copy-done.1 = f32[8]{0} copy-done((f32[8]{0}, u32[]) %all-gather-start.2)",
     False),
])
def test_collective_found_by_the_hlo_in_its_stats(name, text, want):
    """A trace may print an op by its bare name and keep its HLO instruction
    in the stats (`long_name`): the opcode there decides, as for kernels."""
    ev = _dev(name, 0, 10, text)
    assert tr.is_collective(ev) is want
    red = tr.reduce([_host(tr.WINDOW, 0, 100), ev], chips=1)
    assert red.collective_s == pytest.approx(10e-9 if want else 0.0, abs=1e-15)
