"""Reduction of a JAX profiler trace to the benchmark's device numbers.

One place computes, from the `.xplane.pb` file that `jax.profiler` writes,
every number the per-layer metrics read:

  * the traced window: the span of the benchmark's own `bench.window`
    annotation on the host;
  * device busy time: the union of the intervals in which an operation
    runs on a device (the `XLA Ops` line of each `/device:TPU:n` plane),
    clipped to the window, averaged over the cell's chips, so that a chip
    that ran nothing counts as idle;
  * kernel time: the summed device durations of the Pallas kernels' events,
    per chip.
    The program gives its `pallas_call`s no name, so a kernel is found by
    what the trace prints for it: an XLA op whose own HLO instruction is a
    custom call to `KERNEL_TARGET`.  The ops of the jitted wrappers around
    a kernel (reshapes, pads, the fusions that read its output) are not
    kernel time, even where their names mention the wrapper;
  * collective time: the summed device durations, per chip, of the
    collective ops: an op whose own HLO instruction's opcode is one of
    `COLLECTIVES` (each also as its async `-start` and `-done` halves).
    The opcode, not the name: JAX names a psum's all-reduce `psum.N`;
  * `breakdown`: the device operations that took the most time, under the
    names the trace prints (an XLA op's HLO name, its `.N` suffix dropped so
    that the chunks of one kernel add up), per chip, and the longest idle
    gaps of any chip (one that ran nothing is idle the whole window), each
    named by the innermost event of the host thread that ran the window
    (the benchmark's `bench.*` annotations, the program's spans, JAX's own
    dispatch events) that covers the gap's midpoint.

Event times in `ProfileData` share one clock across host and device planes.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Event", "Reduction", "find_xplane", "load", "reduce"]

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
KERNEL_TARGET = "tpu_custom_call"
TOP = 10
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_COLLECTIVE = "(%s)(-start|-done)?" % "|".join(map(re.escape, COLLECTIVES))
# The opcode follows the instruction's shape after a space and opens its operand
# list; an operand or a called computation is named with a leading `%`.
_COLLECTIVE_OPCODE_RE = re.compile(r" = .*?(?<= )%s\(" % _COLLECTIVE)
_COLLECTIVE_NAME_RE = re.compile(r"^%s$" % _COLLECTIVE)


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""  # the op's HLO-related stats, for kernel matching

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    kernel_s: float
    kernel_events: int
    devices: int  # devices that ran anything in the window
    device_ops: list  # [[name, seconds per chip], ...] most time first
    idle_gaps: list  # [[host span, seconds], ...] longest first
    collective_s: float  # per chip


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stat_text(ev) -> str:
    parts = []
    for key, value in ev.stats:
        if isinstance(value, str) and key in ("hlo_op", "long_name", "tf_op", "hlo_category",
                                              "name", "kernel_details", "source"):
            parts.append(value)
    return " ".join(parts)


def load(path: str) -> list[Event]:
    """Device op events and host events of a trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name, float(ev.start_ns),
                                 float(ev.duration_ns), _stat_text(ev) if device else ""))
    return out


def _union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def op_name(name: str) -> str:
    """`%mttkrp_pallas_call.28 = f32[...] custom-call(...)` -> `mttkrp_pallas_call`."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _is_kernel(ev: Event) -> bool:
    """The op's HLO (`%x = ... custom-call(...), custom_call_target="..."`),
    printed as its name or in its stats, names the Mosaic target."""
    return f'custom_call_target="{KERNEL_TARGET}"' in f"{ev.name} {ev.text}"


def is_collective(ev: Event) -> bool:
    """The op's HLO instruction (`%psum.3 = f32[8]{0} all-reduce(...)`),
    printed as its name or in its stats, is a collective; so is an op the
    trace names by a bare collective opcode (`all-reduce.1`)."""
    return bool(_COLLECTIVE_OPCODE_RE.search(f"{ev.name} {ev.text}")
                or _COLLECTIVE_NAME_RE.match(op_name(ev.name)))


def reduce(events: list[Event], *, chips: int, window: str = WINDOW) -> Reduction:
    """The window's device numbers, per chip of the `chips` the cell runs on
    (more, should more devices have run anything)."""
    host = [e for e in events if e.plane.startswith("/host:")]
    marks = [e for e in host if e.name == window]
    if not marks:
        raise ValueError(f"the trace holds no {window!r} annotation")
    w0, w1 = marks[0].start_ns, marks[0].end_ns
    ops = [e for e in events if e.plane.startswith("/device:")
           and e.end_ns > w0 and e.start_ns < w1]
    per_device: dict[str, list] = {}
    for e in ops:
        per_device.setdefault(e.plane, []).append((max(e.start_ns, w0), min(e.end_ns, w1)))
    devices = max(chips, len(per_device))

    def per_chip(evs) -> float:
        return sum(min(e.end_ns, w1) - max(e.start_ns, w0) for e in evs) / devices

    busy = sum(_union_ns(iv) for iv in per_device.values()) / devices
    kernels = [e for e in ops if _is_kernel(e)]
    kernel = per_chip(kernels)
    collective = per_chip(e for e in ops if is_collective(e))

    by_name: dict[str, list] = {}
    for e in ops:
        by_name.setdefault(op_name(e.name), []).append(e)
    device_ops = sorted(((n, per_chip(evs)) for n, evs in by_name.items()),
                        key=lambda kv: -kv[1])[:TOP]

    # Idle gaps of every chip, named by the host span.
    gaps = [(w0, w1)] * (devices - len(per_device))
    for intervals in per_device.values():
        end = w0
        for s, e in sorted(intervals):
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if w1 > end:
            gaps.append((end, w1))
    spans = [e for e in host if e.line == marks[0].line and e.name != window and e.dur_ns > 0]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = 0.5 * (s + e)
        cover = [h for h in spans if h.start_ns <= mid <= h.end_ns]
        name = min(cover, key=lambda h: h.dur_ns).name if cover else "(no host span)"
        named.append([name, (e - s) * 1e-9])
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy * 1e-9,
        kernel_s=kernel * 1e-9,
        kernel_events=len(kernels),
        devices=len(per_device),
        device_ops=[[n, t * 1e-9] for n, t in device_ops],
        idle_gaps=named,
        collective_s=collective * 1e-9,
    )
