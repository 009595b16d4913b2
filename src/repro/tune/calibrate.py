"""Measured-roofline PMS calibration: fit a `TPUSpec` to this machine.

The PMS (core/pms.py) prices every candidate controller configuration with
two hardware constants — `hbm_bw` and `peak_flops_f32` — that ship as TPU
v5e datasheet guesses.  PR 8's `obs.calibrate` join made the resulting
mispredictions visible (`achieved_pct` of ~1e-3 % on CPU interpret-mode
Pallas); this module closes the loop the way the paper's PMS intends: run
microbenchmarks once per backend, fit the constants from measured sweep
timings, persist the fitted spec (`repro.tune.cache`), and let
`pms.search(spec="measured")` search with numbers the machine actually
achieves.

Two measurement layers, combined by `calibrate()`:

  * **Microbenchmarks** (`benchmarks/roofline.py`-style): a jitted
    streaming-copy kernel for raw memory bandwidth and a jitted
    segment-matmul — shaped like the Pallas kernel's one-hot
    `(tile_i, blk) @ (blk, R_pad)` MXU step — for raw f32 FLOP/s.  These
    bound what the backend can do, and serve as the fallback when the
    least-squares fit is degenerate.
  * **Block-sweep fit**: run the planned CP-ALS sweep at several controller
    configurations, read each workspace's *exact* per-plan byte and FLOP
    counts off the PMS itself (a unit-constant `TPUSpec` turns
    `pms_estimates()` into a byte/FLOP counter), and least-squares fit
    ``t_measured ≈ bytes / hbm_bw + flops / peak_flops_f32``.  The fitted
    constants are *effective* rates — they absorb whatever per-block
    overhead the execution path has (the CPU interpreter, most visibly) —
    which is exactly what makes the PMS's predictions land near measured
    wall-clock.

Validation rides PR 8's join: `calibrate()` re-prices every measured sample
through `obs.calibrate.CalibrationRow` under both the default and the fitted
spec, so the result carries its own achieved_pct evidence
(`CalibrationResult.validation`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import numpy as np

from ..core.memctrl import (
    CacheEngineConfig,
    DMAEngineConfig,
    MemoryControllerConfig,
    TPUSpec,
)
from ..obs import trace as _trace
from .cache import AutotuneCache, current_backend, default_cache

__all__ = [
    "CalibSample",
    "CalibrationResult",
    "DEFAULT_CALIBRATION_CFGS",
    "measure_hbm_bw",
    "measure_peak_flops_f32",
    "roofline_counts",
    "sweep_sample",
    "fit_spec",
    "predicted_seconds",
    "calibrate",
    "calibrate_and_store",
    "resolve_spec",
]

#: The unit-constant spec that turns the PMS predictors into byte/FLOP
#: counters: with hbm_bw == peak_flops_f32 == 1, `t_mem` IS the byte count
#: and `t_compute` IS the FLOP count.
_UNIT_SPEC = TPUSpec(hbm_bw=1.0, peak_flops_f32=1.0)

#: Controller configurations the block-sweep fit runs at.  tile_i varies the
#: FLOP/byte ratio (the segment-matmul term scales with the output tile, the
#: stream term does not), blk varies the block count — together they give the
#: least-squares system two well-separated columns.
DEFAULT_CALIBRATION_CFGS: tuple[MemoryControllerConfig, ...] = (
    MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=128, tile_j=128, tile_k=128),
        dma=DMAEngineConfig(blk=128),
    ),
    MemoryControllerConfig(),  # the 256-cube default
    MemoryControllerConfig(
        cache=CacheEngineConfig(tile_i=512, tile_j=512, tile_k=512),
        dma=DMAEngineConfig(blk=512),
    ),
)


# ---------------------------------------------------------------------------
# Microbenchmarks
# ---------------------------------------------------------------------------


def measure_hbm_bw(nbytes: int = 1 << 26, reps: int = 3) -> float:
    """Raw streaming bandwidth (bytes/s) of the default backend: a jitted
    elementwise copy-scale over an `nbytes` f32 buffer (one read + one write
    per element), best of `reps` timed calls after a compile warmup."""
    import jax
    import jax.numpy as jnp

    n = max(1, nbytes // 4)
    x = jnp.ones((n,), jnp.float32)
    stream = jax.jit(lambda a: a * 1.0001 + 1.0)
    jax.block_until_ready(stream(x))  # compile
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(stream(x))
        best = min(best, time.perf_counter() - t0)
    return (2 * 4 * n) / best


def measure_peak_flops_f32(
    tile: int = 512, blk: int = 2048, lanes: int = 512, reps: int = 3
) -> float:
    """Raw f32 FLOP/s of the default backend via a jitted segment-matmul
    shaped like the kernel's MXU step — a `(tile, blk) @ (blk, lanes)`
    product (2*tile*blk*lanes FLOPs), best of `reps` after warmup."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (tile, blk), jnp.float32)
    b = jax.random.normal(key, (blk, lanes), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    jax.block_until_ready(mm(a, b))  # compile
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(mm(a, b))
        best = min(best, time.perf_counter() - t0)
    return (2.0 * tile * blk * lanes) / best


# ---------------------------------------------------------------------------
# Block-sweep samples
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CalibSample:
    """One measured sweep at one controller configuration: the exact PMS
    byte/FLOP counts of the built workspace (per output mode, so the
    max-form roofline can be re-priced under any spec) plus the measured
    steady-state seconds per sweep."""

    label: str
    per_mode: tuple[tuple[float, float], ...]  # (mem_bytes, flops) per mode
    measured_s: float

    @property
    def mem_bytes(self) -> float:
        return float(sum(b for b, _ in self.per_mode))

    @property
    def flops(self) -> float:
        return float(sum(f for _, f in self.per_mode))


def roofline_counts(ws) -> tuple[tuple[float, float], ...]:
    """Exact (mem_bytes, flops) per output mode of a planned workspace, read
    off the PMS predictors with the unit-constant spec (measured fills and
    padding, not the analytic occupancy model)."""
    ests = ws.pms_estimates(_UNIT_SPEC)
    return tuple(
        (float(ests[m].t_mem), float(ests[m].t_compute)) for m in sorted(ests)
    )


def predicted_seconds(
    per_mode: Sequence[tuple[float, float]], spec: TPUSpec
) -> float:
    """Re-price stored byte/FLOP counts under a spec with the PMS's max-form
    roofline (per-mode max(t_mem, t_compute), summed over the sweep)."""
    return float(
        sum(max(b / spec.hbm_bw, f / spec.peak_flops_f32) for b, f in per_mode)
    )


def _cfg_label(cfg: MemoryControllerConfig) -> str:
    c, d = cfg.cache, cfg.dma
    return f"tiles=({c.tile_i},{c.tile_j},{c.tile_k}),blk={d.blk}"


def sweep_sample(
    st, rank: int, cfg: MemoryControllerConfig, *, reps: int = 2,
    seed: int = 0,
) -> CalibSample:
    """Build the planned CP-ALS workspace at `cfg`, time its steady-state
    jitted sweep (one compile + one warm call, then best of `reps`), and
    pair the measurement with the workspace's exact byte/FLOP counts."""
    import jax
    import jax.numpy as jnp

    from ..core.coo import random_factors
    from ..kernels.ops import make_planned_cp_als

    ws = make_planned_cp_als(st, rank, cfg=cfg)
    per_mode = roofline_counts(ws)
    facs = ws.pad_factors(random_factors(jax.random.PRNGKey(seed), st.shape, rank))
    idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
    nxs = jnp.asarray(float(np.sum(st.values.astype(np.float64) ** 2)), jnp.float32)
    facs, lam, fit = ws.sweep(facs, idx, val, nxs, first=True)  # compile
    facs, lam, fit = ws.sweep(facs, idx, val, nxs, first=False)  # steady compile
    jax.block_until_ready(fit)
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        facs, lam, fit = ws.sweep(facs, idx, val, nxs, first=False)
        jax.block_until_ready(fit)
        best = min(best, time.perf_counter() - t0)
    return CalibSample(label=_cfg_label(cfg), per_mode=per_mode, measured_s=best)


# ---------------------------------------------------------------------------
# Least-squares fit
# ---------------------------------------------------------------------------


def fit_spec(
    samples: Sequence[CalibSample],
    base: TPUSpec = TPUSpec(),
    *,
    fallback_hbm_bw: float | None = None,
    fallback_peak_flops: float | None = None,
) -> TPUSpec:
    """Least-squares fit of (hbm_bw, peak_flops_f32) from measured sweeps.

    Solves ``t_i ≈ bytes_i * x0 + flops_i * x1`` for x = (1/hbm_bw,
    1/peak_flops_f32) over the samples' total byte/FLOP counts.  The sum
    form is the fit model (it upper-bounds the PMS's max-form roofline and
    keeps the system linear); the fitted constants are then used inside the
    unchanged max-form predictors.  If a coefficient comes back
    non-positive (collinear samples, or one term measurement-noise small),
    that constant falls back to the microbenchmark value (or `base`'s) and
    the other is refit alone.  `peak_flops` (bf16) keeps `base`'s
    f32-to-bf16 ratio.  Raises ValueError on an empty sample list."""
    if not samples:
        raise ValueError("fit_spec needs at least one calibration sample")
    B = np.array([s.mem_bytes for s in samples], dtype=np.float64)
    F = np.array([s.flops for s in samples], dtype=np.float64)
    t = np.array([s.measured_s for s in samples], dtype=np.float64)
    if np.any(t <= 0):
        raise ValueError("calibration samples must have measured_s > 0")
    A = np.stack([B, F], axis=1)
    x, *_ = np.linalg.lstsq(A, t, rcond=None)
    inv_bw, inv_pf = float(x[0]), float(x[1])
    if inv_bw <= 0 and inv_pf <= 0:
        # Degenerate system: keep the fallbacks for both.
        inv_bw = 1.0 / (fallback_hbm_bw or base.hbm_bw)
        inv_pf = 1.0 / (fallback_peak_flops or base.peak_flops_f32)
    elif inv_pf <= 0:
        inv_pf = 1.0 / (fallback_peak_flops or base.peak_flops_f32)
        inv_bw = float(np.dot(B, t - F * inv_pf) / np.dot(B, B))
        inv_bw = max(inv_bw, np.finfo(np.float64).tiny)
    elif inv_bw <= 0:
        inv_bw = 1.0 / (fallback_hbm_bw or base.hbm_bw)
        inv_pf = float(np.dot(F, t - B * inv_bw) / np.dot(F, F))
        inv_pf = max(inv_pf, np.finfo(np.float64).tiny)
    bf16_ratio = base.peak_flops / base.peak_flops_f32
    fitted_f32 = 1.0 / inv_pf
    return dataclasses.replace(
        base,
        hbm_bw=1.0 / inv_bw,
        peak_flops_f32=fitted_f32,
        peak_flops=fitted_f32 * bf16_ratio,
    )


# ---------------------------------------------------------------------------
# The end-to-end calibration workflow
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CalibrationResult:
    """Everything one calibration run learned: the fitted spec, the raw
    measurements behind it, the microbenchmark peaks, and the
    `obs.calibrate` validation rows (achieved_pct under the default vs the
    fitted spec, per sample)."""

    spec: TPUSpec
    backend: str
    samples: tuple[CalibSample, ...]
    stream_hbm_bw: float | None
    matmul_peak_flops_f32: float | None
    validation: tuple[dict, ...]

    @property
    def residual_rel(self) -> float:
        """Mean relative error of the fitted sum-form model over the
        calibration samples (the fit's own goodness measure)."""
        errs = []
        for s in self.samples:
            pred = s.mem_bytes / self.spec.hbm_bw + s.flops / self.spec.peak_flops_f32
            errs.append(abs(pred - s.measured_s) / s.measured_s)
        return float(np.mean(errs)) if errs else float("nan")


def _validation_rows(
    samples: Sequence[CalibSample], fitted: TPUSpec, base: TPUSpec, preset: str
) -> tuple[dict, ...]:
    """Re-price every sample through PR 8's join (`obs.calibrate`
    CalibrationRow) under the default and the fitted spec."""
    from ..obs.calibrate import CalibrationRow

    rows = []
    for s in samples:
        default = CalibrationRow(
            format="cp", preset=preset,
            predicted_s=predicted_seconds(s.per_mode, base),
            measured_s=s.measured_s,
        )
        measured = CalibrationRow(
            format="cp", preset=preset,
            predicted_s=predicted_seconds(s.per_mode, fitted),
            measured_s=s.measured_s,
        )
        rows.append({
            "label": s.label,
            "measured_s": s.measured_s,
            "achieved_pct_default": default.achieved_pct,
            "achieved_pct_measured": measured.achieved_pct,
        })
    return tuple(rows)


def calibrate(
    preset: str = "tiny",
    *,
    rank: int = 8,
    cfgs: Sequence[MemoryControllerConfig] = DEFAULT_CALIBRATION_CFGS,
    reps: int = 2,
    base: TPUSpec = TPUSpec(),
    microbench: bool = True,
    seed: int = 0,
) -> CalibrationResult:
    """Run the full calibration workflow on the default backend: (optional)
    microbenchmarks, one block-sweep sample per configuration in `cfgs`, the
    least-squares fit, and the `obs.calibrate` validation join.  Does not
    touch the on-disk cache — `calibrate_and_store` persists."""
    from ..core.coo import frostt_like

    backend = current_backend()
    with _trace.span("tune_calibrate", backend=backend, preset=preset):
        bw = measure_hbm_bw() if microbench else None
        pf = measure_peak_flops_f32() if microbench else None
        st = frostt_like(preset)
        samples = tuple(
            sweep_sample(st, rank, cfg, reps=reps, seed=seed)
            for cfg in cfgs
        )
        fitted = fit_spec(
            samples, base, fallback_hbm_bw=bw, fallback_peak_flops=pf
        )
        return CalibrationResult(
            spec=fitted,
            backend=backend,
            samples=samples,
            stream_hbm_bw=bw,
            matmul_peak_flops_f32=pf,
            validation=_validation_rows(samples, fitted, base, preset),
        )


#: Smaller workload for the implicit `spec="measured"` cache-miss path: one
#: rep, two configurations, no medium sweeps — seconds, not minutes.
QUICK_CALIBRATION_KWARGS = dict(
    preset="tiny", rank=8, cfgs=DEFAULT_CALIBRATION_CFGS[:2], reps=1
)


def calibrate_and_store(
    *, cache: AutotuneCache | None = None, **kwargs
) -> CalibrationResult:
    """`calibrate()` + persist the fitted spec for this backend in the
    autotune cache (so `pms.search(spec="measured")` finds it)."""
    cache = cache if cache is not None else default_cache()
    result = calibrate(**kwargs)
    cache.put_spec(
        result.backend,
        result.spec,
        fitted_at=time.time(),
        residual_rel=result.residual_rel,
        stream_hbm_bw=result.stream_hbm_bw,
        matmul_peak_flops_f32=result.matmul_peak_flops_f32,
        n_samples=len(result.samples),
    )
    return result


def resolve_spec(
    spec, *, cache: AutotuneCache | None = None, calibrate_on_miss: bool = True
):
    """Resolve the `spec=` argument every PMS entry point accepts:

      * a `TPUSpec` passes through;
      * ``"default"`` is the published constants of the chip this runs on
        (`repro.platform.device_spec`);
      * ``"measured"`` is this backend's fitted spec from the autotune
        cache — on a cache miss, a quick calibration runs and persists
        (`QUICK_CALIBRATION_KWARGS`) when `calibrate_on_miss` is set,
        otherwise ValueError.
    """
    if isinstance(spec, TPUSpec):
        return spec
    if spec == "default":
        from ..platform import device_spec

        return device_spec()
    if spec != "measured":
        raise ValueError(
            f"unknown spec {spec!r}: expected a TPUSpec, 'default' or 'measured'"
        )
    cache = cache if cache is not None else default_cache()
    found = cache.get_spec(current_backend())
    if found is not None:
        return found
    if not calibrate_on_miss:
        raise ValueError(
            f"no fitted spec for backend {current_backend()!r} in "
            f"{cache.path}; run repro.tune.calibrate_and_store() (or "
            f"scripts/calibrate.py) first"
        )
    return calibrate_and_store(cache=cache, **QUICK_CALIBRATION_KWARGS).spec
