"""repro.obs — observability for the planned decomposition engine.

Three stdlib-only pieces (docs/observability.md):

  * `obs.trace`   — span/event tracing: `span("plan_build", mode=..)`
    context managers recorded into a thread-safe collector, exported as
    JSONL or Chrome-trace JSON, bridged into `jax.profiler.TraceAnnotation`
    so device work lines up in xprof.  Off by default; enabled by
    ``REPRO_TRACE=1`` (or a path), `trace.enable()`, or per call via
    ``decompose(..., trace=...)``.  Disabled calls are no-ops.
  * `obs.metrics` — always-on counters/gauges/histograms recorded by the
    hot paths: drive-loop iteration times, plan-build and padding stats,
    the kernels' HBM fetch bytes per mode, plan-cache hit/miss/eviction
    latencies, guard/restart/fallback/admission events, shard imbalance.
  * `obs.calibrate` — joins the PMS `predict_*` estimates against measured
    sweep times (`achieved_pct`); feeds the `pms_accuracy` section of
    BENCH_kernel.json and `scripts/trace_report.py --pms`.

This package imports nothing from the rest of `repro` at module scope
(`calibrate` resolves its `core.pms` / `bench` imports lazily), so every
layer — including `repro.core` — can record into it without cycles.
"""
from . import metrics  # noqa: F401
from .trace import (  # noqa: F401
    Tracer,
    active,
    configure_from_env,
    disable,
    enable,
    event,
    install,
    span,
    tracing,
)

__all__ = [
    "metrics",
    "Tracer",
    "active",
    "configure_from_env",
    "disable",
    "enable",
    "event",
    "install",
    "span",
    "tracing",
]
