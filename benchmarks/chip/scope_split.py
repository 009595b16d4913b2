"""Device time per sweep scope, and device idle time per phase of a job,
from a JAX profiler trace and the program's sweep scope map.

Beside trace_reduce.py, whose events and window it reuses:

  * scopes: the program wraps each mode's kernel call, each mode's update and
    the fit of its single-device sweeps in `jax.named_scope`s
    (`<fmt>.m<n>.kernel`, `<fmt>.m<n>.update`, `<fmt>.fit`).  A device op
    event carries its HLO instruction name and no metadata, so the program
    gives the map: `PlannedWorkspace.sweep_scopes()`, per compiled sweep
    program its module name and {instruction name: scope or None} over
    every instruction.  An op belongs to the module execution on its
    device's `XLA Modules` line that covers it, printed `<module>(<id>)`.
    The id is not one the program can read, and CP's two programs share a
    module name, so the execution's program is the one of that name that
    holds the most of the instructions that ran in it; should two such
    programs name any of those ops' scopes differently, the split refuses
    to choose.  An op with no scope in it, or not in it (a TPU execution
    can run an instruction the compiled text does not print), counts as
    `UNSCOPED`.
  * idle phases: the first device's idle gaps inside the window (as
    trace_reduce counts them), split where they cross the program's spans on
    the window's host thread: `iteration` from the end of each `drive`'s
    first `sweep` span to the end of its last (the fit read-back and the
    next dispatch), `job` elsewhere inside a `decompose` span (init, index
    upload, pad, the first sweep's wait, unpad), `outside` the rest (the
    benchmark's own loop).
"""
from __future__ import annotations

import dataclasses
import re

import trace_reduce
from trace_reduce import Event

__all__ = ["MODULES_LINE", "PHASES", "ScopeSplit", "load_modules", "split"]

MODULES_LINE = "XLA Modules"
PHASES = ("iteration", "job", "outside")
UNSCOPED = "(unscoped)"
_MODULE_RE = re.compile(r"^(.*)\(\d+\)$")


@dataclasses.dataclass
class ScopeSplit:
    scope_s: dict  # {scope: device seconds in the window}, sweep programs only
    sweep_s: float  # device seconds of every op of the sweep programs
    idle_s: dict  # {phase: idle seconds of the first device}


def load_modules(path: str) -> list[Event]:
    """The module executions (`XLA Modules` line) of every device plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out.extend(Event(plane.name, line.name, ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)) for ev in line.events)
    return out


def instruction(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


def _overlap(s: float, e: float, intervals) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in intervals)


def _program(name: str, ops: list[Event], programs: list[dict]) -> dict:
    """The scope map of the program of module `name` that holds the most of
    the instructions that ran in one execution (`ops`)."""
    names = {instruction(e.name) for e in ops}
    held = [(len(names & p["scopes"].keys()), p["scopes"]) for p in programs
            if p["module"] == name]
    most = max(n for n, _ in held)
    best = [sc for n, sc in held if n == most]
    if any(sc.get(i) != best[0].get(i) for sc in best[1:] for i in names):
        raise ValueError(f"two {name!r} programs ran the same ops under other scopes")
    return best[0]


def split(events: list[Event], modules: list[Event], programs: list[dict],
          window: str = trace_reduce.WINDOW) -> ScopeSplit:
    """`events` from `trace_reduce.load`, `modules` from `load_modules`,
    `programs` from `PlannedWorkspace.sweep_scopes()`."""
    host = [e for e in events if e.plane.startswith("/host:")]
    mark = next((e for e in host if e.name == window), None)
    if mark is None:
        raise ValueError(f"the trace holds no {window!r} annotation")
    w0, w1 = mark.start_ns, mark.end_ns
    ops = sorted((e for e in events if e.plane.startswith("/device:")
                  and e.end_ns > w0 and e.start_ns < w1), key=lambda e: e.start_ns)

    names = {p["module"] for p in programs}
    scope_s: dict[str, float] = {}
    sweep = 0.0
    for m in modules:
        hit = _MODULE_RE.match(m.name)
        if hit is None or hit[1] not in names or m.end_ns <= w0 or m.start_ns >= w1:
            continue
        ran = [e for e in ops if e.plane == m.plane
               and m.start_ns <= e.start_ns and e.end_ns <= m.end_ns]
        scopes = _program(hit[1], ran, programs)
        for e in ran:
            dur = (min(e.end_ns, w1) - max(e.start_ns, w0)) * 1e-9
            key = scopes.get(instruction(e.name)) or UNSCOPED
            scope_s[key] = scope_s.get(key, 0.0) + dur
            sweep += dur

    # Idle gaps of the first device that ran anything, as trace_reduce finds them.
    gaps = []
    first = next((e.plane for e in ops), None)
    end = w0
    for s, t in sorted((max(e.start_ns, w0), min(e.end_ns, w1)) for e in ops if e.plane == first):
        if s > end:
            gaps.append((end, s))
        end = max(end, t)
    if w1 > end:
        gaps.append((end, w1))

    spans = [e for e in host if e.line == mark.line and e.dur_ns > 0]
    jobs = [(e.start_ns, e.end_ns) for e in spans if e.name == "decompose"]
    iters = []
    for d in (e for e in spans if e.name == "drive"):
        ends = sorted(e.end_ns for e in spans if e.name == "sweep"
                      and d.start_ns <= e.start_ns and e.end_ns <= d.end_ns)
        if len(ends) > 1:
            iters.append((ends[0], ends[-1]))
    idle = dict.fromkeys(PHASES, 0.0)
    for s, t in gaps:
        in_iter, in_job = _overlap(s, t, iters), _overlap(s, t, jobs)
        idle["iteration"] += in_iter * 1e-9
        idle["job"] += (in_job - in_iter) * 1e-9
        idle["outside"] += (t - s - in_job) * 1e-9
    return ScopeSplit(scope_s=scope_s, sweep_s=sweep, idle_s=idle)
