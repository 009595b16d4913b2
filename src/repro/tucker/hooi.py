"""Sparse Tucker decomposition (HOOI) on the programmable memory controller.

The second real workload of the substrate: the paper designs the Tensor
Remapper / per-mode layouts / PMS to be *programmable*, i.e. reusable across
tensor-decomposition kernels, and sparse Tucker exercises exactly the same
irregular-access problem through the TTM chain (Jiang et al., "Sparse Tucker
Tensor Decomposition on a Hybrid FPGA-CPU Platform").  HOOI (higher-order
orthogonal iteration):

    repeat:
      for each mode n:
        Y_(n) = X_(n) (kron of U^(m), m != n)     # sparse TTMc — the kernel
        U^(n) = top-R_n left singular vectors of Y_(n)
      G = Y_(N-1) x_{N-1} U^(N-1)^T               # core, free from the last Y
      fit = 1 - sqrt(||X||^2 - ||G||^2) / ||X||   # factors orthonormal

The truncated SVD runs through the *unfolding Gram*: G_Y = Y^T Y is only
(P x P) with P = prod of the other core ranks, so the eigh never touches an
I_n-sized matrix; U^(n) = Y V_top diag(1/sigma_top) recovers the left
singular vectors (classic tall-matrix economy SVD).

Two methods, mirroring cp_als:
  * 'pallas'    — the planned TTM-chain kernel (kernels/ttm_pallas.py) on a
                  `PlannedTucker` workspace: one PMS-tunable BlockPlan +
                  device-resident layout per output mode, built once and
                  reused across every HOOI iteration (plan amortization,
                  exactly the PlannedCPALS posture).  jit_sweep=True runs
                  each iteration as one compiled sweep with rank-padded,
                  device-resident factors; jit_sweep=False keeps the eager
                  per-mode dispatch loop as the parity baseline.
  * 'reference' — the pure-jnp TTMc oracle (kernels/ref.py), also available
                  as a jitted whole-iteration sweep.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.coo import SparseTensor
from ..core.loop import (
    check_drive_extras,
    check_planned_method,
    check_workspace,
    f32_matmuls,
    finish_iter,
    require_sharded_sweep,
)
from ..core.memctrl import MemoryControllerConfig, TPUSpec
from ..kernels.ops import PlannedTTMC, make_planned_ttmc, planned_layout_bytes
from ..kernels.mttkrp_pallas import rank_padded
from ..kernels.ref import ttmc_ref
from ..kernels.workspace import PlannedWorkspace, plan_stream, sweep_scope
from ..obs import trace as _trace

__all__ = [
    "TuckerState",
    "tucker_hooi",
    "PlannedTucker",
    "make_planned_tucker",
    "init_tucker_factors",
    "core_fit_value",
]


@dataclasses.dataclass
class TuckerState:
    factors: list[jax.Array]  # one (I_m, R_m) per mode, orthonormal columns
    core: jax.Array  # (R_0, ..., R_{N-1}) in natural mode order
    fit_history: list[float]

    @property
    def core_ranks(self) -> tuple[int, ...]:
        return tuple(int(s) for s in self.core.shape)


def _validated_core_ranks(st: SparseTensor, core_ranks: Sequence[int]) -> tuple[int, ...]:
    cr = tuple(int(r) for r in core_ranks)
    if len(cr) != st.nmodes:
        raise ValueError(
            f"core_ranks has {len(cr)} entries for a {st.nmodes}-mode tensor"
        )
    for m, (r, s) in enumerate(zip(cr, st.shape)):
        if not 1 <= r <= s:
            raise ValueError(
                f"core rank {r} for mode {m} out of range [1, {s}] (mode length)"
            )
        others = math.prod(cr[k] for k in range(len(cr)) if k != m)
        if r > others:
            raise ValueError(
                f"core rank {r} for mode {m} exceeds the product of the other "
                f"ranks ({others}): the mode-{m} unfolding of the core cannot "
                f"have full row rank"
            )
    return cr


def init_tucker_factors(
    key: jax.Array, shape: Sequence[int], core_ranks: Sequence[int], dtype=jnp.float32
) -> list[jax.Array]:
    """Random *orthonormal* factor matrices (reduced QR of a Gaussian), one
    (I_m, R_m) per mode — HOOI's fit formula assumes orthonormal columns from
    the first iteration."""
    keys = jax.random.split(key, len(shape))
    facs = []
    for k, s, r in zip(keys, shape, core_ranks):
        q, _ = jnp.linalg.qr(jax.random.normal(k, (int(s), int(r)), dtype))
        facs.append(q)
    return facs


def _factor_from_unfolding(y: jax.Array, r: int) -> jax.Array:
    """Top-r left singular vectors of the unfolding y (I_n, P) via eigh of
    the (P, P) Gram — the truncated SVD never materializes an I_n x I_n
    matrix.  Columns with (relatively) vanishing singular values are zeroed
    rather than divided by ~0; HOOI only uses the spanned subspace."""
    g = y.T @ y
    w, v = jnp.linalg.eigh(g)  # ascending eigenvalues
    top_v = v[:, ::-1][:, :r]
    sigma = jnp.sqrt(jnp.maximum(w[::-1][:r], 0.0))
    thresh = jnp.maximum(sigma[0], 1e-30) * 1e-7
    inv = jnp.where(sigma > thresh, 1.0 / jnp.maximum(sigma, thresh), 0.0)
    return y @ (top_v * inv[None, :])


def _core_from_unfolding(
    y: jax.Array, u: jax.Array, mode: int, core_ranks: tuple[int, ...]
) -> jax.Array:
    """Fold U^(mode)^T Y_(mode) back into the (R_0, ..., R_{N-1}) core in
    natural mode order (Y's columns are row-major over ascending input
    mode)."""
    nmodes = len(core_ranks)
    in_modes = tuple(m for m in range(nmodes) if m != mode)
    mat = u.T @ y  # (R_mode, P)
    core = mat.reshape((core_ranks[mode],) + tuple(core_ranks[m] for m in in_modes))
    axes = (mode,) + in_modes  # axes[k] = the tensor mode of core axis k
    perm = tuple(axes.index(m) for m in range(nmodes))
    return jnp.transpose(core, perm)


def core_fit_value(core: jax.Array, norm_x_sq: jax.Array) -> jax.Array:
    """fit = 1 - ||X - X_hat|| / ||X||.  With orthonormal factors,
    ||X - X_hat||^2 = ||X||^2 - ||G||^2 — no pass over the non-zeros."""
    resid_sq = jnp.maximum(norm_x_sq - jnp.sum(core * core), 0.0)
    return 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)


@partial(jax.jit, static_argnames=("shape", "core_ranks"))
def _sweep_reference(factors, idx, val, norm_x_sq, *, shape, core_ranks):
    """One full jitted HOOI iteration on the pure-jnp TTMc oracle: every
    mode's TTMc -> Gram eigh -> factor update, plus core + fit, in a single
    compiled function."""
    factors = list(factors)
    y = None
    for m in range(len(shape)):
        y = ttmc_ref(idx, val, factors, m, shape[m])
        factors[m] = _factor_from_unfolding(y, core_ranks[m])
    last = len(shape) - 1
    core = _core_from_unfolding(y, factors[last], last, core_ranks)
    return tuple(factors), core, core_fit_value(core, norm_x_sq)


@dataclasses.dataclass
class PlannedTucker(PlannedWorkspace):
    """Per-mode plan cache driving the whole HOOI loop on the memory
    controller — the Tucker mirror of `PlannedCPALS`.

    One `PlannedTTMC` per output mode — each holds its own remapped,
    device-resident copy of the non-zero stream — constructed once and reused
    for every HOOI iteration.  The steady-state iteration is `sweep`: one
    jitted function running a full HOOI iteration (every mode's TTMc -> Gram
    eigh -> factor update, plus the core fold and fit).  Padding/residency
    (each mode to its own rank_padded(R_m)) and the host drive loop come
    from `PlannedWorkspace` — this class supplies only the HOOI sweep body.
    """

    ops: dict[int, PlannedTTMC]
    shape: tuple[int, ...]
    core_ranks: tuple[int, ...]

    @property
    def lane_ranks(self) -> tuple[int, ...]:
        return self.core_ranks

    def plan_for(self, mode: int):
        return self.ops[mode].plan

    def _geoms(self) -> dict:
        return {m: op.plan for m, op in self.ops.items()}

    def _layout_bytes(self) -> int:
        return planned_layout_bytes(self.ops)

    def _build_sweep(self) -> Callable:
        shape, core_ranks, nmodes = self.shape, self.core_ranks, self.nmodes
        rps, prows = self.rank_pads, self.padded_rows
        ops = self.ops

        def sweep(layouts, facs, norm_x_sq):
            facs = list(facs)
            last = nmodes - 1
            for m in range(nmodes):
                op, p = ops[m], ops[m].plan
                with sweep_scope("tucker", "kernel", m):
                    in_facs = tuple(
                        facs[im][: p.in_rows[n]] for n, im in enumerate(p.in_modes)
                    )
                    out = op.call_padded(in_facs, layouts[m])
                with sweep_scope("tucker", "update", m):
                    y = out[: shape[m], : op.out_cols]
                    u = _factor_from_unfolding(y, core_ranks[m])
                    # Re-pad in place of the old padded factor (padding rows
                    # and lanes stay exactly zero, so the next mode's kernel
                    # gathers zeros for padding elements).
                    facs[m] = (
                        jnp.zeros((prows[m], rps[m]), u.dtype)
                        .at[: shape[m], : core_ranks[m]]
                        .set(u)
                    )
                    if m == last:
                        u_last = facs[last][: shape[last], : core_ranks[last]]
                        core = _core_from_unfolding(y, u_last, last, core_ranks)
            with sweep_scope("tucker", "fit"):
                fit = core_fit_value(core, norm_x_sq)
            return tuple(facs), core, fit

        return jax.jit(sweep)

    def sweep(self, facs, norm_x_sq):
        """One jitted HOOI iteration in padded space.  Returns
        (new padded factors, core, fit scalar on device)."""
        return super().sweep(facs, norm_x_sq)

    def vmem_model_bytes(self) -> int:
        return max(
            op.cfg.vmem_bytes_ttmc(
                rank_padded(math.prod(op.in_ranks)),
                tuple(rank_padded(r) for r in op.in_ranks),
            )
            for op in self.ops.values()
        )

    def pms_estimates(self, spec: TPUSpec = TPUSpec()) -> dict:
        """Per-mode exact PMS estimates from the built plans (the
        `obs.calibrate` hook — see PlannedCPALS.pms_estimates)."""
        from ..core.pms import predict_ttmc

        return {
            m: predict_ttmc(op.plan, self.core_ranks, op.cfg, spec)
            for m, op in self.ops.items()
        }

    def _build_fallback_sweep(self) -> Callable:
        """Reference degradation target of the "fallback" guard policy: the
        jitted `_sweep_reference` body on the SAME padded factors.  The HOOI
        sweep takes no stream arguments (the remapped copies live in the
        plans), so the COO stream is reconstructed from a host-side plan —
        padding slots carry value 0 and contribute nothing."""
        idx, val = plan_stream(self.ops[0].plan)
        idx, val = jnp.asarray(idx), jnp.asarray(val)
        shape, core_ranks, nmodes = self.shape, self.core_ranks, self.nmodes
        rps, prows = self.rank_pads, self.padded_rows

        def sweep(idx, val, facs, norm_x_sq):
            facs = list(facs)
            y = None
            for m in range(nmodes):
                true = [f[:s, :r] for f, s, r in zip(facs, shape, core_ranks)]
                y = ttmc_ref(idx, val, true, m, shape[m])
                u = _factor_from_unfolding(y, core_ranks[m])
                facs[m] = (
                    jnp.zeros((prows[m], rps[m]), u.dtype)
                    .at[: shape[m], : core_ranks[m]]
                    .set(u)
                )
            last = nmodes - 1
            u_last = facs[last][: shape[last], : core_ranks[last]]
            core = _core_from_unfolding(y, u_last, last, core_ranks)
            return tuple(facs), core, core_fit_value(core, norm_x_sq)

        jitted = jax.jit(sweep)
        return lambda facs, *args, it: jitted(idx, val, facs, *args)


def make_planned_tucker(
    st: SparseTensor,
    core_ranks: Sequence[int],
    *,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> PlannedTucker:
    """Build the full HOOI workspace: one tuned TTMc plan per output mode.

    With auto_tune=True each mode gets its own PMS-selected controller
    configuration scored for the TTMc kernel (core-tensor tile in the VMEM
    model); otherwise `cfg` (or the default) is shared by every mode."""
    cr = _validated_core_ranks(st, core_ranks)
    ops = {
        m: make_planned_ttmc(
            st, m, cr, cfg=cfg, auto_tune=auto_tune, spec=spec
        )
        for m in range(st.nmodes)
    }
    return PlannedTucker(ops=ops, shape=st.shape, core_ranks=cr)


@f32_matmuls
def tucker_hooi(
    st: SparseTensor,
    core_ranks: Sequence[int],
    *,
    iters: int = 10,
    method: str = "pallas",
    seed: int = 0,
    tol: float | None = None,
    planned: "PlannedTucker | None" = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = "default",
    cfg: MemoryControllerConfig | None = None,
    jit_sweep: bool = True,
    devices: int | None = None,
    dist=None,
    verbose: bool = False,
    guards=None,
    checkpoint_every: int | None = None,
    checkpoint_path=None,
) -> TuckerState:
    """Run sparse Tucker HOOI.

    method: 'pallas' — the planned TTM-chain memory-controller kernel: a
            `PlannedTucker` workspace is built once (one remapped,
            device-resident BlockPlan per output mode) and reused for every
            iteration; 'pallas_sharded' — the distributed planned path
            (repro.dist.planned): per-mode balanced stream partitions,
            shard-local layouts, one jitted shard_map sweep per iteration
            with a single psum of the partial TTMc unfolding per mode;
            'reference' — the pure-jnp TTMc oracle.
    planned / auto_tune / cfg: pallas-path knobs — pass a
            prebuilt `PlannedTucker` (or `ShardedPlannedTucker`) to reuse
            plans across calls, or let auto_tune run the TTMc-aware PMS per
            mode (worst-shard makespan for the sharded path).
            auto_tune="cached" persists/reuses the winners on disk; spec may
            be a TPUSpec, "default", or "measured" (repro.tune).
    jit_sweep: run each iteration as one jitted sweep (factors stay
            device-resident, rank-padded for the pallas path); False keeps
            the eager per-mode dispatch loop as the parity baseline
            ('pallas_sharded' is sweep-only and rejects jit_sweep=False).
    devices / dist: 'pallas_sharded' placement — a device count for the
            default 1-D `shard` mesh, or an explicit ShardingPlan.
    guards / checkpoint_every / checkpoint_path: the resilience surface of
            the planned drive loop (repro.resilience).  Planned jitted
            paths only.
    """
    cr = _validated_core_ranks(st, core_ranks)
    nmodes = st.nmodes
    with _trace.span("job.init"):
        factors = init_tucker_factors(jax.random.PRNGKey(seed), st.shape, cr)
        norm_x_sq = jnp.asarray(
            float(np.sum(st.values.astype(np.float64) ** 2)), jnp.float32)
    fits: list[float] = []

    check_planned_method(method, planned, devices, dist)
    check_drive_extras(method, jit_sweep, guards, checkpoint_every,
                       checkpoint_path)
    if method == "pallas_sharded":
        require_sharded_sweep(jit_sweep)
        from ..kernels.ops import ShardedPlannedTucker, make_sharded_planned_tucker

        if planned is None:
            planned = make_sharded_planned_tucker(
                st, cr, dist=dist, devices=devices, cfg=cfg,
                auto_tune=auto_tune, spec=spec,
            )
        else:
            check_workspace(
                planned, ShardedPlannedTucker, method,
                {"shape": st.shape, "core_ranks": cr}, devices=devices,
            )
        factors, core, fits = planned.drive(
            factors, (norm_x_sq,), iters=iters, tol=tol, verbose=verbose,
            label="tucker_hooi", guards=guards,
            checkpoint_every=checkpoint_every, checkpoint_path=checkpoint_path,
        )
        return TuckerState(factors=factors, core=core, fit_history=fits)
    if method == "pallas":
        if planned is None:
            planned = make_planned_tucker(
                st, cr, cfg=cfg, auto_tune=auto_tune, spec=spec,
            )
        else:
            check_workspace(
                planned, PlannedTucker, method,
                {"shape": st.shape, "core_ranks": cr},
            )
        if jit_sweep:
            # Fast path: factors padded once, updated in padded space by one
            # jitted sweep per iteration; sliced back only for the state.
            factors, core, fits = planned.drive(
                factors, (norm_x_sq,), iters=iters, tol=tol, verbose=verbose,
                label="tucker_hooi", guards=guards,
                checkpoint_every=checkpoint_every,
                checkpoint_path=checkpoint_path,
            )
            return TuckerState(factors=factors, core=core, fit_history=fits)
    elif method != "reference":
        raise ValueError(f"unknown method {method!r}: expected 'pallas' or 'reference'")

    if method == "reference":
        # Only the reference oracle walks the raw COO stream; the pallas
        # paths consume the per-mode device-resident plan layouts instead,
        # so the transfer would duplicate HBM the plans already hold.
        idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)

    if method == "reference" and jit_sweep:
        factors_t = tuple(factors)
        core = None
        for it in range(iters):
            factors_t, core, fit = _sweep_reference(
                factors_t, idx, val, norm_x_sq, shape=st.shape, core_ranks=cr
            )
            if finish_iter(fits, fit, it, tol, verbose, "tucker_hooi"):
                break
        return TuckerState(factors=list(factors_t), core=core, fit_history=fits)

    # Eager per-mode dispatch loop: jit_sweep=False (both methods).
    core = None
    for it in range(iters):
        y = None
        for m in range(nmodes):
            if method == "pallas":
                y = planned.ops[m].output(factors, st.shape[m])
            else:
                y = ttmc_ref(idx, val, factors, m, st.shape[m])
            factors[m] = _factor_from_unfolding(y, cr[m])
        last = nmodes - 1
        core = _core_from_unfolding(y, factors[last], last, cr)
        if finish_iter(fits, core_fit_value(core, norm_x_sq), it, tol, verbose, "tucker_hooi"):
            break
    return TuckerState(factors=factors, core=core, fit_history=fits)
