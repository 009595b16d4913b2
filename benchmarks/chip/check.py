"""The comparison that decides `correct`, shared by the harness and by the
readings script that sets its limits.

A job of the window returns the program's state after its last iteration
and the fits `drive` reported.  The same workspace replays the job with one
iteration fewer (same seed, same compiled programs), which gives the state
that last iteration started from; its fits must equal the job's first ones
bit for bit.  The format's float64 reference then recomputes the last
iteration from that state, teacher-forced (`reference/<format>.py`), and
the gaps are held to the cell's limits (`limits/<workload>.json`).
"""
from __future__ import annotations

import importlib

import numpy as np

from reference import numerics

__all__ = ["state_arrays", "reference_for", "tensor", "program_gaps", "control_gaps",
           "judge"]

STATE_KEYS = ("factors", "lam", "core", "cores")


def state_arrays(state) -> dict:
    """The program's result as host arrays: CP {"factors", "lam"}, Tucker
    {"factors", "core"}, TT {"cores"}."""
    out = {}
    for key in STATE_KEYS:
        value = getattr(state, key, None)
        if value is None:
            continue
        out[key] = [np.asarray(v) for v in value] if isinstance(value, (list, tuple)) else np.asarray(value)
    return out


def reference_for(fmt: str):
    return importlib.import_module(f"reference.{fmt}")


def tensor(ar, idx, vals) -> dict:
    """The COO tensor in a reference arithmetic; ||X||^2 summed in float64
    on the host, as every decomposition's fit needs it."""
    return {
        "idx": ar.put_index(idx),
        "vals": ar.put(vals),
        "norm_x_sq": ar.put(np.sum(np.asarray(vals, np.float64) ** 2)),
    }


def program_gaps(fmt, idx, vals, before: dict, after: dict, fit: float) -> dict:
    """The program's last iteration against the float64 reference."""
    ref, ar = reference_for(fmt), numerics.exact()
    with ar.scope():
        return ref.compare(ar, tensor(ar, idx, vals), before, after, fit)


def control_gaps(fmt, idx, vals, before: dict) -> dict:
    """The control: the reference in the program's place at the precision
    one step below the configuration's (`numerics.high`), judged the same
    way."""
    ref, hi = reference_for(fmt), numerics.high()
    with hi.scope():
        _, state, fit = ref.iteration(hi, tensor(hi, idx, vals), before)
        after = {k: ([np.asarray(a) for a in v] if isinstance(v, list) else np.asarray(v))
                 for k, v in state.items()}
    return program_gaps(fmt, idx, vals, before, after, fit)


def judge(gaps: dict, limits: dict) -> tuple[bool, dict]:
    """(every compared number finite and within its limit, {name: {"value",
    "limit"}}) for the numbers `limits` names."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = float(gaps[name])
        ok = ok and bool(np.isfinite(value)) and value <= limit
        checks[name] = {"value": value, "limit": float(limit)}
    return ok, checks
