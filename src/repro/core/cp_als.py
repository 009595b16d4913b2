"""CP-ALS driver (paper Alg. 1) built on the spMTTKRP substrate.

Faithful to the paper's system framing:
  * one tensor copy, remapped into the next output mode's order before each
    mode's MTTKRP (Alg. 5) — `layout="remap"`; or
  * one pre-sorted copy per mode (the alternative the paper rejects on FPGA
    for memory reasons; on TPU HBM it is a legitimate space/time trade) —
    `layout="copies"`.

The steady-state iteration is one jitted *sweep* — a single compiled function
running every mode's MTTKRP -> gram -> solve -> normalize plus the on-device
fit (`_sweep_streams` / `_sweep_remap` here; `PlannedCPALS.sweep` for the
Pallas memory-controller path).  Only the `tol` early-exit reads the
per-iteration fit scalar back to the host.  Pass `jit_sweep=False` (or an
`mttkrp_fn` override) to fall back to the eager per-mode dispatch loop.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .coo import SparseTensor, to_device, random_factors
from .loop import (
    check_drive_extras,
    check_planned_method,
    check_workspace,
    f32_matmuls,
    finish_iter,
    require_sharded_sweep,
)
from ..obs import trace as _trace
from .mttkrp import mttkrp, hadamard_rows
from .remap import remap_stable

__all__ = ["CPState", "cp_als", "fit_value", "gram_hadamard"]


@dataclasses.dataclass
class CPState:
    factors: list[jax.Array]  # one (I_m, R) per mode
    lam: jax.Array  # (R,) column norms
    fit_history: list[float]

    @property
    def rank(self) -> int:
        return int(self.lam.shape[0])


def gram_hadamard(factors: Sequence[jax.Array], mode: int) -> jax.Array:
    """Hadamard product of Gram matrices F_n^T F_n for all n != mode. (R, R)."""
    g = None
    for n, f in enumerate(factors):
        if n == mode:
            continue
        gn = f.T @ f
        g = gn if g is None else g * gn
    assert g is not None
    return g


def _solve(mttkrp_out: jax.Array, g: jax.Array, ridge: float = 1e-8) -> jax.Array:
    """A = M @ (G + ridge I)^-1 ; ridge keeps near-rank-deficient iterations
    stable (G is PSD)."""
    r = g.shape[0]
    gi = g + ridge * jnp.eye(r, dtype=g.dtype)
    return jax.scipy.linalg.solve(gi, mttkrp_out.T, assume_a="pos").T


def _normalize(f: jax.Array, it: int) -> tuple[jax.Array, jax.Array]:
    """Column-normalize; first iteration uses the standard CP-ALS
    max(norm, 1) convention: the initial random factors can carry tiny
    column norms on poorly scaled tensors, and dividing by them inflates
    noise columns before the scale has been absorbed into lambda.  Later
    iterations normalize by the exact column 2-norm (guarded against 0)."""
    norms = jnp.linalg.norm(f, axis=0)
    if it == 0:
        norms = jnp.maximum(norms, 1.0)
    else:
        norms = jnp.where(norms > 1e-12, norms, 1.0)
    return f / norms, norms


def inner_with_model(
    indices: jax.Array, values: jax.Array, factors: Sequence[jax.Array], lam: jax.Array
) -> jax.Array:
    """<X, [[lam; factors]]> evaluated only at the non-zeros (exact, since the
    model is dense but X is zero elsewhere ... the inner product only needs
    X's support)."""
    prod = None
    for n, f in enumerate(factors):
        rows = f[indices[:, n]]
        prod = rows if prod is None else prod * rows
    return jnp.sum(values * (prod @ lam))


def model_norm_sq(factors: Sequence[jax.Array], lam: jax.Array) -> jax.Array:
    """||[[lam; factors]]||_F^2 = lam^T (hadamard_n F_n^T F_n) lam."""
    g = None
    for f in factors:
        gn = f.T @ f
        g = gn if g is None else g * gn
    return lam @ g @ lam


def fit_value(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    lam: jax.Array,
    norm_x_sq: jax.Array,
) -> jax.Array:
    """fit = 1 - ||X - X_hat|| / ||X||."""
    inner = inner_with_model(indices, values, factors, lam)
    resid_sq = jnp.maximum(norm_x_sq + model_norm_sq(factors, lam) - 2.0 * inner, 0.0)
    return 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)


def _update_mode(mt: jax.Array, factors: list, m: int, first: bool):
    """Shared mode update: gram -> solve -> normalize (one Alg. 1 step)."""
    g = gram_hadamard(factors, m)
    f = _solve(mt, g)
    f, lam = _normalize(f, 0 if first else 1)
    factors[m] = f
    return factors, lam


@partial(jax.jit, static_argnames=("shape", "method", "first"))
def _sweep_streams(factors, streams_idx, streams_val, norm_x_sq, *, shape, method, first):
    """One full jitted ALS iteration over per-mode pre-sorted streams
    (layout='copies'): every mode's MTTKRP -> gram -> solve -> normalize,
    plus the fit, in a single compiled function."""
    factors = list(factors)
    lam = None
    for m in range(len(shape)):
        mt = mttkrp(streams_idx[m], streams_val[m], factors, m, shape[m], method=method)
        factors, lam = _update_mode(mt, factors, m, first)
    fit = fit_value(streams_idx[-1], streams_val[-1], factors, lam, norm_x_sq)
    return tuple(factors), lam, fit


@partial(jax.jit, static_argnames=("shape", "method", "first"))
def _sweep_remap(factors, idx, val, norm_x_sq, *, shape, method, first):
    """One full jitted ALS iteration for the single-stream layout: the
    on-device Tensor Remapper (Alg. 5) re-sorts the carried stream before
    each mode inside the same compiled function; the remapped stream is
    returned as carry for the next iteration."""
    factors = list(factors)
    lam = None
    for m in range(len(shape)):
        idx, val, _ = remap_stable(idx, val, m)
        mt = mttkrp(idx, val, factors, m, shape[m], method=method)
        factors, lam = _update_mode(mt, factors, m, first)
    fit = fit_value(idx, val, factors, lam, norm_x_sq)
    return tuple(factors), lam, idx, val, fit


@f32_matmuls
def cp_als(
    st: SparseTensor,
    rank: int,
    *,
    iters: int = 10,
    method: str = "approach1",
    layout: str = "remap",
    seed: int = 0,
    tol: float | None = None,
    mttkrp_fn: Callable | None = None,
    planned=None,
    auto_tune: bool | str = False,
    spec="default",
    cfg=None,
    jit_sweep: bool = True,
    devices: int | None = None,
    dist=None,
    verbose: bool = False,
    guards=None,
    checkpoint_every: int | None = None,
    checkpoint_path=None,
) -> CPState:
    """Run CP-ALS.

    method: 'approach1' | 'approach2'  (Sec. 3 compute patterns), or
            'pallas' — the memory-controller kernel: a `PlannedCPALS`
            workspace (kernels/ops.py) is built once — one remapped,
            device-resident BlockPlan per output mode — and reused for every
            iteration (plan amortization, Alg. 1 on the Alg. 5 layout); or
            'pallas_sharded' — the distributed planned path
            (repro.dist.planned): the stream is partitioned into balanced
            output-tile ranges per mode, each shard's remapped layout is
            device-local, and every iteration is one jitted shard_map sweep
            with a single psum of partial factor rows per mode.
    layout: 'remap'  — single stream, remapped (re-sorted) before each mode
                       (Alg. 5; remap runs on device via remap_stable);
            'copies' — per-mode pre-sorted copies (more HBM, no remap traffic).
            Ignored for the pallas paths: the per-mode plans *are* the copies.
    mttkrp_fn: optional override with signature (indices, values, factors,
               mode, out_rows) -> (I_mode, R).  Forces the eager loop (the
               override may not be jit-traceable).
    planned / auto_tune / cfg: pallas-path knobs — pass a
               prebuilt `PlannedCPALS` (or `ShardedPlannedCPALS` for
               'pallas_sharded') to reuse plans across calls, or let
               auto_tune run the PMS per mode (Sec. 5.3; worst-shard
               makespan for the sharded path).  auto_tune="cached" persists
               and reuses the PMS winners on disk (repro.tune.cache).
    spec:      PMS hardware constants — a TPUSpec, "default" (datasheet
               guesses), or "measured" (this backend's calibrated spec from
               the autotune cache; see repro.tune).
    jit_sweep: run each iteration as one jitted sweep (factors stay
               device-resident — rank-padded for the pallas path — across
               iterations; `tol` is checked on the host against the
               per-iteration fit scalar).  False restores the eager per-mode
               dispatch loop, kept as the parity baseline ('pallas_sharded'
               is sweep-only and rejects jit_sweep=False).
    devices / dist: 'pallas_sharded' placement — a device count for the
               default 1-D `shard` mesh, or an explicit ShardingPlan.
    guards / checkpoint_every / checkpoint_path: the resilience surface of
               the planned drive loop (repro.resilience): a `GuardConfig`
               for divergence detection + raise/restart/fallback recovery,
               and periodic checkpointing with automatic resume.  Planned
               jitted paths only.
    """
    if layout not in ("remap", "copies"):
        raise ValueError(f"unknown layout {layout!r}: expected 'remap' or 'copies'")
    nmodes = st.nmodes
    with _trace.span("job.init"):
        factors = random_factors(jax.random.PRNGKey(seed), st.shape, rank)
        lam = jnp.ones((rank,), jnp.float32)
        norm_x_sq = jnp.asarray(
            float(np.sum(st.values.astype(np.float64) ** 2)), jnp.float32)
    fits: list[float] = []

    check_planned_method(method, planned, devices, dist)
    # mttkrp_fn forces the eager loop, which never reaches drive's
    # guard/checkpoint surface — fold it into the jit_sweep condition.
    check_drive_extras(method, jit_sweep and mttkrp_fn is None, guards,
                       checkpoint_every, checkpoint_path)
    if method == "pallas_sharded":
        if mttkrp_fn is not None:
            raise ValueError("mttkrp_fn cannot override the sharded planned path")
        require_sharded_sweep(jit_sweep)
        from ..kernels.ops import ShardedPlannedCPALS, make_sharded_planned_cp_als

        if planned is None:
            planned = make_sharded_planned_cp_als(
                st, rank, dist=dist, devices=devices, cfg=cfg,
                auto_tune=auto_tune, spec=spec,
            )
        else:
            check_workspace(
                planned, ShardedPlannedCPALS, method,
                {"shape": st.shape, "rank": rank}, devices=devices,
            )
        factors, lam, fits = planned.drive(
            factors, (norm_x_sq,), iters=iters, tol=tol, verbose=verbose,
            label="cp_als", guards=guards,
            checkpoint_every=checkpoint_every, checkpoint_path=checkpoint_path,
        )
        return CPState(factors=factors, lam=lam, fit_history=fits)
    if method == "pallas" and mttkrp_fn is None:
        # Lazy import: kernels builds on core, not the other way around.
        from ..kernels.ops import PlannedCPALS, make_planned_cp_als

        if planned is None:
            planned = make_planned_cp_als(
                st, rank, cfg=cfg, auto_tune=auto_tune, spec=spec,
            )
        else:
            check_workspace(
                planned, PlannedCPALS, method, {"shape": st.shape, "rank": rank}
            )
        if jit_sweep:
            # Fast path: factors padded once, updated in padded space by one
            # jitted sweep per iteration; sliced back only for the CPState.
            with _trace.span("job.upload"):
                base_idx, base_val = jnp.asarray(st.indices), jnp.asarray(st.values)
            factors, lam, fits = planned.drive(
                factors, (base_idx, base_val, norm_x_sq), iters=iters, tol=tol,
                verbose=verbose, label="cp_als", guards=guards,
                checkpoint_every=checkpoint_every,
                checkpoint_path=checkpoint_path,
            )
            return CPState(factors=factors, lam=lam, fit_history=fits)
        mttkrp_fn = planned.mttkrp_fn
        layout = "planned"

    if layout == "planned":
        # The per-mode remapped copies live inside the plans; keep one
        # (order-irrelevant) stream only for the fit computation.
        base_idx, base_val = jnp.asarray(st.indices), jnp.asarray(st.values)
    elif layout == "copies":
        streams = []
        for m in range(nmodes):
            sm = st.sorted_by(m)
            streams.append((jnp.asarray(sm.indices), jnp.asarray(sm.values)))
    else:
        # Single stream; keep it sorted by the *previous* output mode and
        # remap on device before each mode, exactly Alg. 5.
        s0 = st.sorted_by(0)
        cur_idx, cur_val = jnp.asarray(s0.indices), jnp.asarray(s0.values)

    if jit_sweep and mttkrp_fn is None and layout in ("copies", "remap"):
        factors_t = tuple(factors)
        if layout == "copies":
            streams_idx = tuple(s[0] for s in streams)
            streams_val = tuple(s[1] for s in streams)
        for it in range(iters):
            if layout == "copies":
                factors_t, lam, fit = _sweep_streams(
                    factors_t, streams_idx, streams_val, norm_x_sq,
                    shape=st.shape, method=method, first=(it == 0),
                )
            else:
                factors_t, lam, cur_idx, cur_val, fit = _sweep_remap(
                    factors_t, cur_idx, cur_val, norm_x_sq,
                    shape=st.shape, method=method, first=(it == 0),
                )
            if finish_iter(fits, fit, it, tol, verbose, "cp_als"):
                break
        return CPState(factors=list(factors_t), lam=lam, fit_history=fits)

    # Eager per-mode dispatch loop: mttkrp_fn overrides and jit_sweep=False.
    def do_mttkrp(indices, values, facs, mode):
        if mttkrp_fn is not None:
            return mttkrp_fn(indices, values, facs, mode, st.shape[mode])
        return mttkrp(indices, values, facs, mode, st.shape[mode], method=method)

    for it in range(iters):
        for m in range(nmodes):
            if layout == "planned":
                idx, val = base_idx, base_val
            elif layout == "copies":
                idx, val = streams[m]
            else:
                idx, val, _ = remap_stable(cur_idx, cur_val, m)  # Tensor Remapper
                cur_idx, cur_val = idx, val
            mt = do_mttkrp(idx, val, factors, m)
            g = gram_hadamard(factors, m)
            f = _solve(mt, g)
            f, lam = _normalize(f, it)
            factors[m] = f
        if finish_iter(fits, fit_value(idx, val, factors, lam, norm_x_sq), it, tol, verbose, "cp_als"):
            break
    return CPState(factors=factors, lam=lam, fit_history=fits)
