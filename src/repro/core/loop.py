"""Shared host-side driver pieces for the decomposition loops.

Every format driver (`cp_als`, `tucker_hooi`, `tt_als`) runs the same outer
shape: validate the method/workspace arguments, pad the factors once, call one
jitted sweep per iteration, read a single fit scalar back for the tol
early-exit, and unpad at materialization.  The per-iteration bookkeeping and
the argument contracts live here so the drivers stay format-specific only in
their math — `repro.kernels.workspace.PlannedWorkspace.drive` is the matching
device-side loop.

This module is importable from `repro.core` (it must not import
`repro.kernels`: kernels builds on core, not the other way around) — workspace
classes are passed in as arguments where needed.

The numerical-guard surface of the resilience layer also lives here
(`GuardConfig` / `GuardState` / `DecompositionDiverged`): divergence detection
is pure host-side fit bookkeeping, so it sits next to `finish_iter` and is
consumed by `PlannedWorkspace.drive` and re-exported from `repro.resilience`.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings

import jax

from ..obs import metrics as _metrics
from ..obs import trace as _trace

__all__ = [
    "f32_matmuls",
    "finish_iter",
    "check_planned_method",
    "check_drive_extras",
    "require_sharded_sweep",
    "check_workspace",
    "GuardConfig",
    "GuardState",
    "DecompositionDiverged",
]

GUARD_POLICIES = ("raise", "fallback", "restart")


def f32_matmuls(driver):
    """Run a format driver with every matmul it traces at full f32
    precision.  At default precision a TPU rounds f32 matmul operands to
    bf16 (8 mantissa bits) for the grams, solves and fits of the normal
    equations; the CPU always multiplies in f32, so this changes nothing
    there.  (The kernels set the precision of their own dots.)"""

    @functools.wraps(driver)
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return driver(*args, **kwargs)

    return run

#: A fit must drop this far below the best seen before an iteration counts
#: toward the divergence patience — plain convergence noise stays inert.
REGRESSION_TOL = 1e-6


class DecompositionDiverged(RuntimeError):
    """A guarded decomposition detected divergence and could not (or was not
    asked to) recover.  Carries the diagnostic context the multi-tenant
    engine needs to report the incident: which driver, at which iteration,
    why, and the fit trajectory up to the failure."""

    def __init__(self, label: str, iteration: int, reason: str,
                 fit_history: list[float]):
        self.label = label
        self.iteration = iteration
        self.reason = reason
        self.fit_history = list(fit_history)
        super().__init__(
            f"[{label}] diverged at iteration {iteration}: {reason} "
            f"(fit history: {self._tail()})"
        )

    def _tail(self) -> str:
        tail = self.fit_history[-4:]
        pre = "..., " if len(self.fit_history) > len(tail) else ""
        return "[" + pre + ", ".join(f"{f:.6g}" for f in tail) + "]"


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Numerical-guard policy for `PlannedWorkspace.drive`.

    policy:
      * "raise"    — raise `DecompositionDiverged` with diagnostics;
      * "restart"  — re-initialize with jittered factors and retry the whole
                     decomposition, at most `max_restarts` times;
      * "fallback" — degrade the pallas sweep to the reference sweep mid-run,
                     reusing the same padded factors (last good iterate).
    divergence_patience: consecutive fit-regression iterations tolerated
      before the guard fires (non-finite fit always fires immediately).
    max_restarts: bound on "restart" retries before escalating to raise.
    check_factors_every: if > 0, additionally check factor finiteness every k
      iterations (one extra host sync per check); 0 disables the factor check
      (the fit check is free — the fit scalar is already synced every
      iteration).
    """

    policy: str = "raise"
    divergence_patience: int = 3
    max_restarts: int = 2
    check_factors_every: int = 0

    def __post_init__(self):
        if self.policy not in GUARD_POLICIES:
            raise ValueError(
                f"unknown guard policy {self.policy!r}: expected one of "
                f"{GUARD_POLICIES}"
            )
        if self.divergence_patience < 1:
            raise ValueError("divergence_patience must be >= 1")
        if self.max_restarts < 0:
            raise ValueError("max_restarts must be >= 0")
        if self.check_factors_every < 0:
            raise ValueError("check_factors_every must be >= 0")


class GuardState:
    """Host-side divergence tracker: feed it the per-iteration fit scalar
    (`observe_fit`) and it returns a non-None reason string when the guard
    should fire.  `reset()` clears the trajectory state (after a restart or a
    fallback rebase) but keeps the restart budget."""

    def __init__(self, cfg: GuardConfig):
        self.cfg = cfg
        self.restarts = 0
        self.reset()

    def reset(self) -> None:
        self.best = -math.inf
        self.regress_streak = 0

    def observe_fit(self, fit: float) -> str | None:
        if not math.isfinite(fit):
            return f"non-finite fit ({fit})"
        if fit < self.best - REGRESSION_TOL:
            self.regress_streak += 1
            if self.regress_streak >= self.cfg.divergence_patience:
                return (
                    f"fit regressed below best {self.best:.6g} for "
                    f"{self.regress_streak} consecutive iterations "
                    f"(latest {fit:.6g})"
                )
        else:
            self.regress_streak = 0
            self.best = max(self.best, fit)
        return None


def finish_iter(fits, fit, it: int, tol, verbose: bool, label: str) -> bool:
    """Host-side bookkeeping per iteration: record the fit scalar and decide
    the tol early-exit (the only device->host sync in the jitted loops).

    A non-finite fit terminates the loop immediately (returns True) and is
    surfaced as a RuntimeWarning even with guards off — it used to fail the
    tol comparison silently and burn every remaining iteration.  The same
    incident is recorded as a structured obs event + counter
    (`resilience.nonfinite_fit`), so resilience actions are countable
    across a run, not just printed."""
    fits.append(float(fit))
    if verbose:
        print(f"[{label}] iter {it:3d} fit={fits[-1]:.6f}")
    if not math.isfinite(fits[-1]):
        _metrics.counter("resilience.nonfinite_fit", label=label).inc()
        _trace.event("nonfinite_fit", label=label, it=it, fit=repr(fits[-1]))
        warnings.warn(
            f"[{label}] non-finite fit ({fits[-1]}) at iteration {it}; "
            f"stopping early — pass guards=GuardConfig(...) for "
            f"raise/restart/fallback recovery",
            RuntimeWarning,
            stacklevel=2,
        )
        return True
    return tol is not None and it > 0 and abs(fits[-1] - fits[-2]) < tol


def check_planned_method(method: str, planned, devices, dist) -> None:
    """The argument contract every planned driver shares: a workspace only
    makes sense for the pallas paths, and placement arguments only for the
    sharded one — both would otherwise be silently ignored."""
    if planned is not None and method not in ("pallas", "pallas_sharded"):
        raise ValueError(
            "a planned workspace was passed but method is not 'pallas' / "
            "'pallas_sharded'; the workspace would be silently ignored"
        )
    if method != "pallas_sharded" and (devices is not None or dist is not None):
        raise ValueError(
            f"devices/dist apply only to method='pallas_sharded' (got "
            f"method={method!r}); they would be silently ignored"
        )


def check_drive_extras(method: str, jit_sweep: bool, guards,
                       checkpoint_every, checkpoint_path) -> None:
    """The resilience kwargs (guards / checkpoint) are consumed by the
    planned `drive` loop only; reject combinations that would silently
    ignore them (mirrors `check_planned_method`)."""
    if guards is None and checkpoint_every is None and checkpoint_path is None:
        return
    if method not in ("pallas", "pallas_sharded") or not jit_sweep:
        raise ValueError(
            "guards/checkpoint_every/checkpoint_path are consumed by the "
            "planned drive loop: they require method='pallas' or "
            "'pallas_sharded' with jit_sweep=True (they would be silently "
            "ignored here)"
        )


def require_sharded_sweep(jit_sweep: bool) -> None:
    if not jit_sweep:
        raise ValueError(
            "method='pallas_sharded' runs only as the jitted shard_map "
            "sweep; use method='pallas' for the eager parity baseline"
        )


def check_workspace(planned, cls, method: str, attrs: dict, devices=None) -> None:
    """Validate a caller-supplied workspace against the call: right class for
    the method, built for the same tensor geometry/ranks, spanning the
    requested device count.  `attrs` maps attribute name -> the value this
    call requires (compared against the workspace's attribute)."""
    if not isinstance(planned, cls):
        extra = (
            ""
            if method == "pallas_sharded"
            else " (use method='pallas_sharded' for sharded workspaces)"
        )
        raise ValueError(
            f"method={method!r} needs a {cls.__name__} workspace, got "
            f"{type(planned).__name__}{extra}"
        )
    if any(getattr(planned, k) != v for k, v in attrs.items()):
        built = " ".join(f"{k}={getattr(planned, k)}" for k in attrs)
        want = " ".join(f"{k}={v}" for k, v in attrs.items())
        raise ValueError(
            f"{cls.__name__} workspace was built for {built}, got {want}"
        )
    if devices is not None and getattr(planned, "nshards", devices) != devices:
        raise ValueError(
            f"{cls.__name__} workspace spans {planned.nshards} shards but "
            f"devices={devices} was requested"
        )
