"""Sparse tensor-train decomposition (TT-ALS) on the programmable memory
controller.

The third workload of the substrate: after CP (MTTKRP) and Tucker (TTMc),
the TT-core update exercises the same irregular-access problem through a
Kronecker of TWO chained interfaces.  TT represents X by N cores
G_k (rl_k, I_k, rr_k) with boundary bonds rl_0 = rr_{N-1} = 1, and ALS
updates one core at a time, left to right:

    repeat:
      for each mode m:
        B_m[i, :] = sum_{z: i_m(z)=i} v_z * kron(l_z, r_z)   # the kernel
        A_m       = kron(P_{m-1}, Q_{m+1})                   # interface Grams
        W_m       = solve(A_m, B_m^T)^T                      # normal equations
        G_m       = fold(W_m)
      fit = 1 - sqrt(||X||^2 + ||TT||^2 - 2<X, TT>) / ||X||

where l_z / r_z are the left/right interface chains of the other cores at
non-zero z, P_{m-1} = (left chain)^T (left chain) is the (rl_m, rl_m) left
Gram (rank-sized — never materialized over prod(I)), and Q_{m+1} the
(rr_m, rr_m) right Gram.  Within one left-to-right sweep the right Grams are
computed once from the incoming cores (cores > m are untouched until the
sweep reaches them) and the left Gram is updated with each freshly solved
core — the standard single-site TT-ALS dataflow.

Core <-> matrix convention used everywhere (kernels included): the mode-m
interface matrix is W_m = transpose(G_m, (1, 0, 2)).reshape(I_m, rl_m*rr_m),
columns row-major over (rl, rr) — rl slow, rr fast — matching the kernel's
kron(l, r) column order and kron(P, Q) normal matrix.

Three methods, mirroring cp_als / tucker_hooi:
  * 'pallas'         — the planned TT-core kernel (kernels/tt_pallas.py) on a
                       `PlannedTT` workspace: one PMS-tunable BlockPlan +
                       device-resident layout per output mode, built once and
                       reused across every ALS iteration.  jit_sweep=True
                       runs each iteration as one compiled sweep with
                       lane-padded, device-resident interface matrices;
                       jit_sweep=False keeps the eager per-mode dispatch loop
                       as the parity baseline.
  * 'pallas_sharded' — the distributed planned path (repro.dist.planned):
                       shard-local layouts, one jitted shard_map sweep per
                       iteration, a single psum of partial B_m rows per mode.
  * 'reference'      — the pure-jnp TT-core oracle (kernels/ref.py), also
                       available as a jitted whole-iteration sweep.
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
from ..kernels.ops import (
    PlannedTTCore,
    _tt_bond_pairs,
    make_planned_ttcore,
    planned_layout_bytes,
)
from ..kernels.ref import ttcore_ref
from ..kernels.workspace import PlannedWorkspace, sweep_scope
from ..obs import trace as _trace

__all__ = [
    "TTState",
    "tt_als",
    "PlannedTT",
    "make_planned_tt",
    "init_tt_cores",
    "tt_svd",
    "core_to_matrix",
    "matrix_to_core",
    "tt_inner",
    "tt_norm_sq",
    "tt_fit_value",
]

# tt_svd densifies the tensor (float64) for the sequential truncated SVD;
# init='auto' falls back to the random init above this element count.
_TT_SVD_DENSE_LIMIT = 1 << 22


@dataclasses.dataclass
class TTState:
    cores: list[jax.Array]  # one (rl_m, I_m, rr_m) per mode; boundary bonds 1
    fit_history: list[float]

    @property
    def tt_ranks(self) -> tuple[int, ...]:
        """The N-1 interior bond ranks."""
        return tuple(int(c.shape[2]) for c in self.cores[:-1])

    def full(self) -> jax.Array:
        """Dense reconstruction (I_0, ..., I_{N-1}) — tiny shapes only."""
        out = self.cores[0]  # (1, I_0, r)
        for c in self.cores[1:]:
            out = jnp.tensordot(out, c, axes=[[-1], [0]])
        return out.reshape(tuple(int(c.shape[1]) for c in self.cores))


def _validated_tt_ranks(st: SparseTensor, tt_ranks: int | Sequence[int]) -> tuple[int, ...]:
    """Normalize/validate the N-1 interior bond ranks (an int broadcasts).
    Bond k sits between modes k and k+1; its rank cannot exceed the matrix
    rank bound min(prod(I_0..I_k), prod(I_{k+1}..I_{N-1}))."""
    if isinstance(tt_ranks, (int, np.integer)):
        tt_ranks = (int(tt_ranks),) * (st.nmodes - 1)
    tr = tuple(int(r) for r in tt_ranks)
    if len(tr) != st.nmodes - 1:
        raise ValueError(
            f"tt_ranks has {len(tr)} entries for a {st.nmodes}-mode tensor "
            f"(pass the N-1 interior TT ranks, or an int to broadcast)"
        )
    for k, r in enumerate(tr):
        bound = min(math.prod(st.shape[: k + 1]), math.prod(st.shape[k + 1 :]))
        if not 1 <= r <= bound:
            raise ValueError(
                f"TT rank {r} for bond {k} (modes {k}|{k + 1}) out of range "
                f"[1, {bound}] (unfolding rank bound)"
            )
    return tr


def core_to_matrix(core: jax.Array) -> jax.Array:
    """G (rl, I, rr) -> W (I, rl*rr), columns row-major over (rl, rr)."""
    rl, i, rr = core.shape
    return jnp.transpose(core, (1, 0, 2)).reshape(i, rl * rr)


def matrix_to_core(w: jax.Array, rl: int, rr: int) -> jax.Array:
    """W (I, rl*rr) -> G (rl, I, rr) — inverse of `core_to_matrix`."""
    return jnp.transpose(w.reshape(w.shape[0], rl, rr), (1, 0, 2))


def init_tt_cores(
    key: jax.Array,
    shape: Sequence[int],
    tt_ranks: Sequence[int],
    dtype=jnp.float32,
) -> list[jax.Array]:
    """Random left-orthogonal TT cores: each core's left unfolding
    (rl*I, rr) is the reduced QR of a Gaussian (plain scaled Gaussian when
    rl*I < rr, where no orthonormal frame exists)."""
    pairs = _tt_bond_pairs(tuple(int(r) for r in tt_ranks), len(shape))
    keys = jax.random.split(key, len(shape))
    cores = []
    for k, s, (rl, rr) in zip(keys, shape, pairs):
        m = jax.random.normal(k, (rl * int(s), rr), dtype)
        if rl * int(s) >= rr:
            m, _ = jnp.linalg.qr(m)
        else:
            m = m / jnp.sqrt(jnp.asarray(float(rr), dtype))
        cores.append(m.reshape(rl, int(s), rr))
    return cores


def tt_svd(st: SparseTensor, tt_ranks: Sequence[int]) -> list[jax.Array]:
    """TT-SVD init (Oseledets): densify, then peel cores off left to right
    by sequential truncated SVD.  Deterministic and near-optimal for the
    given ranks — the standard warm start for TT-ALS.  Rank-deficient
    unfoldings are zero-padded up to the requested bond rank (the padded
    directions carry zero singular value and are refined by ALS).

    Densifies to float64 — guarded to prod(shape) <= 2^22 elements; use
    init='random' beyond that."""
    tr = _validated_tt_ranks(st, tt_ranks)
    nelem = math.prod(st.shape)
    if nelem > _TT_SVD_DENSE_LIMIT:
        raise ValueError(
            f"tt_svd densifies the tensor: prod(shape)={nelem} exceeds the "
            f"{_TT_SVD_DENSE_LIMIT}-element guard; use init='random'"
        )
    shape, nmodes = st.shape, st.nmodes
    dense = np.zeros(shape, np.float64)
    np.add.at(
        dense,
        tuple(st.indices[:, m] for m in range(nmodes)),
        st.values.astype(np.float64),
    )
    cores: list[jax.Array] = []
    c = dense.reshape(1, -1)
    rl = 1
    for k in range(nmodes - 1):
        c = c.reshape(rl * shape[k], -1)
        r = tr[k]
        u, s, vt = np.linalg.svd(c, full_matrices=False)
        keep = min(r, s.shape[0])
        u, s, vt = u[:, :keep], s[:keep], vt[:keep]
        if keep < r:
            u = np.concatenate([u, np.zeros((u.shape[0], r - keep))], axis=1)
            s = np.concatenate([s, np.zeros(r - keep)])
            vt = np.concatenate([vt, np.zeros((r - keep, vt.shape[1]))], axis=0)
        cores.append(jnp.asarray(u.reshape(rl, shape[k], r), jnp.float32))
        c = s[:, None] * vt
        rl = r
    cores.append(jnp.asarray(c.reshape(rl, shape[-1], 1), jnp.float32))
    return cores


def _p_next(p: jax.Array, core: jax.Array) -> jax.Array:
    """Left-interface Gram recursion: P_m = sum_i G_m[:,i,:]^T P_{m-1}
    G_m[:,i,:], shape (rr_m, rr_m)."""
    return jnp.einsum("aib,ac,cid->bd", core, p, core)


def _q_prev(q: jax.Array, core: jax.Array) -> jax.Array:
    """Right-interface Gram recursion: Q_m = sum_i G_m[:,i,:] Q_{m+1}
    G_m[:,i,:]^T, shape (rl_m, rl_m)."""
    return jnp.einsum("aib,bc,dic->ad", core, q, core)


def _q_suffix(cores: Sequence[jax.Array]) -> list[jax.Array]:
    """qs[m] = the right Gram over cores STRICTLY right of m — the Q_{m+1}
    factor of mode m's normal matrix (ones((1,1)) for the last mode).
    Computed once per sweep from the incoming cores."""
    nmodes = len(cores)
    qs = [None] * nmodes
    q = jnp.ones((1, 1), jnp.float32)
    for m in range(nmodes - 1, -1, -1):
        qs[m] = q
        q = _q_prev(q, cores[m])
    return qs


def _solve_core(a: jax.Array, b: jax.Array) -> jax.Array:
    """Solve the core normal equations W A = B for W (I, rl*rr) with A =
    kron(P, Q) symmetric PSD; a trace-scaled ridge keeps the solve finite
    when an interface direction has collapsed."""
    dim = a.shape[0]
    ridge = 1e-8 * (jnp.trace(a) / dim) + 1e-12
    a = a + ridge * jnp.eye(dim, dtype=a.dtype)
    return jax.scipy.linalg.solve(a, b.T, assume_a="pos").T


def tt_inner(indices: jax.Array, values: jax.Array, cores: Sequence[jax.Array]) -> jax.Array:
    """<X, TT> restricted to X's non-zeros: per-nnz left-to-right chain of
    core slices, then the value-weighted sum.  Zero-valued (padding) entries
    contribute exactly nothing."""
    nnz = values.shape[0]
    v = jnp.ones((nnz, 1), jnp.float32)
    for k, core in enumerate(cores):
        rows = jnp.transpose(core, (1, 0, 2))[indices[:, k]]
        v = jnp.einsum("za,zab->zb", v, rows.astype(jnp.float32))
    return jnp.sum(values.astype(jnp.float32) * v[:, 0])


def tt_norm_sq(cores: Sequence[jax.Array]) -> jax.Array:
    """||TT||_F^2 via the left Gram recursion — rank-sized intermediates
    only."""
    p = jnp.ones((1, 1), jnp.float32)
    for core in cores:
        p = _p_next(p, core)
    return p[0, 0]


def tt_fit_value(
    indices: jax.Array,
    values: jax.Array,
    cores: Sequence[jax.Array],
    norm_x_sq: jax.Array,
) -> jax.Array:
    """fit = 1 - ||X - TT|| / ||X||, expanded as ||X||^2 + ||TT||^2 -
    2<X, TT> — one pass over the non-zeros, no densification."""
    resid_sq = jnp.maximum(
        norm_x_sq + tt_norm_sq(cores) - 2.0 * tt_inner(indices, values, cores), 0.0
    )
    return 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)


@partial(jax.jit, static_argnames=("shape",))
def _sweep_reference(cores, idx, val, norm_x_sq, *, shape):
    """One full jitted TT-ALS iteration on the pure-jnp TT-core oracle:
    every mode's B_m -> normal solve -> core update, plus the fit, in a
    single compiled function."""
    cores = list(cores)
    qs = _q_suffix(cores)
    p = jnp.ones((1, 1), jnp.float32)
    for m in range(len(shape)):
        b = ttcore_ref(idx, val, cores, m, shape[m])
        w = _solve_core(jnp.kron(p, qs[m]), b)
        cores[m] = matrix_to_core(w, cores[m].shape[0], cores[m].shape[2])
        p = _p_next(p, cores[m])
    inner = tt_inner(idx, val, cores)
    resid_sq = jnp.maximum(norm_x_sq + p[0, 0] - 2.0 * inner, 0.0)
    fit = 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)
    return tuple(cores), fit


@dataclasses.dataclass
class PlannedTT(PlannedWorkspace):
    """Per-mode plan cache driving the whole TT-ALS loop on the memory
    controller — the tensor-train mirror of `PlannedCPALS`.

    One `PlannedTTCore` per output mode — each holds its own remapped,
    device-resident copy of the non-zero stream — constructed once and
    reused for every ALS iteration.  The steady-state iteration is `sweep`:
    one jitted function running a full left-to-right sweep (every mode's
    TT-core kernel -> kron(P, Q) normal solve -> core update, plus the
    on-device fit).  Padding/residency (each mode's interface matrix to its
    own rank_padded(rl_m*rr_m)) and the host drive loop come from
    `PlannedWorkspace` — this class supplies only the TT sweep body.

    The padded-space factors are the interface MATRICES W_m, not the 3-way
    cores; `tt_als` folds them back at the end."""

    ops: dict[int, PlannedTTCore]
    shape: tuple[int, ...]
    tt_ranks: tuple[int, ...]  # N-1 interior bond ranks

    @property
    def bond_pairs(self) -> tuple[tuple[int, int], ...]:
        return _tt_bond_pairs(self.tt_ranks, self.nmodes)

    @property
    def lane_ranks(self) -> tuple[int, ...]:
        return tuple(a * b for a, b in self.bond_pairs)

    def plan_for(self, mode: int):
        return self.ops[mode].plan

    def _geoms(self) -> dict:
        return {m: op.plan for m, op in self.ops.items()}

    def _layout_bytes(self) -> int:
        return planned_layout_bytes(self.ops)

    def _build_sweep(self) -> Callable:
        shape, nmodes = self.shape, self.nmodes
        pairs, lr = self.bond_pairs, self.lane_ranks
        rps, prows = self.rank_pads, self.padded_rows
        ops = self.ops

        def sweep(layouts, facs, idx, val, norm_x_sq):
            facs = list(facs)
            # Right Grams once from the incoming cores (the first mode's
            # update needs them all); the left Gram runs ahead with each
            # freshly solved core.
            with sweep_scope("tt", "update", 0):
                cores = [
                    matrix_to_core(facs[m][: shape[m], : lr[m]], *pairs[m])
                    for m in range(nmodes)
                ]
                qs = _q_suffix(cores)
                p = jnp.ones((1, 1), jnp.float32)
            for m in range(nmodes):
                op, pln = ops[m], ops[m].plan
                with sweep_scope("tt", "kernel", m):
                    in_mats = tuple(
                        facs[im][: pln.in_rows[n]] for n, im in enumerate(pln.in_modes)
                    )
                    out = op.call_padded(in_mats, layouts[m])
                with sweep_scope("tt", "update", m):
                    b = out[: shape[m], : lr[m]]
                    w = _solve_core(jnp.kron(p, qs[m]), b)
                    cores[m] = matrix_to_core(w, *pairs[m])
                    # Re-pad in place of the old padded matrix (padding rows
                    # and lanes stay exactly zero, so the next mode's kernel
                    # gathers zeros for padding elements).
                    facs[m] = (
                        jnp.zeros((prows[m], rps[m]), w.dtype)
                        .at[: shape[m], : lr[m]]
                        .set(w)
                    )
                    p = _p_next(p, cores[m])
            with sweep_scope("tt", "fit"):
                inner = tt_inner(idx, val, cores)
                resid_sq = jnp.maximum(norm_x_sq + p[0, 0] - 2.0 * inner, 0.0)
                fit = 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)
            return tuple(facs), None, fit

        return jax.jit(sweep)

    def sweep(self, facs, idx, val, norm_x_sq):
        """One jitted TT-ALS iteration in padded space.  Args: `facs` — the
        lane-padded interface matrices; `idx`, `val` — the raw COO stream
        (only the fit's inner product reads it); `norm_x_sq` — ||X||_F^2.
        Returns (new padded matrices, None, fit scalar on device)."""
        return super().sweep(facs, idx, val, norm_x_sq)

    def vmem_model_bytes(self) -> int:
        from ..core.pms import _tt_iface_cols
        from ..kernels.mttkrp_pallas import rank_padded

        return max(
            op.cfg.vmem_bytes_tt(
                rank_padded(op.out_pair[0] * op.out_pair[1]),
                tuple(rank_padded(a * b) for a, b in op.in_rank_pairs),
                _tt_iface_cols(op.in_rank_pairs),
            )
            for op in self.ops.values()
        )

    def pms_estimates(self, spec: TPUSpec = TPUSpec()) -> dict:
        """Per-mode exact PMS estimates from the built plans (the
        `obs.calibrate` hook — see PlannedCPALS.pms_estimates)."""
        from ..core.pms import predict_tt

        return {
            m: predict_tt(op.plan, self.tt_ranks, op.cfg, spec)
            for m, op in self.ops.items()
        }

    def _build_fallback_sweep(self) -> Callable:
        """Reference degradation target of the "fallback" guard policy: the
        same left-to-right sweep as `_build_sweep` with the per-mode Pallas
        TT-core kernels replaced by the pure-jnp `ttcore_ref` oracle on the
        raw stream (drive's args already carry it for the fit).  Operates on
        the SAME padded interface matrices."""
        shape, nmodes = self.shape, self.nmodes
        pairs, lr = self.bond_pairs, self.lane_ranks
        rps, prows = self.rank_pads, self.padded_rows

        def sweep(facs, idx, val, norm_x_sq):
            facs = list(facs)
            cores = [
                matrix_to_core(facs[m][: shape[m], : lr[m]], *pairs[m])
                for m in range(nmodes)
            ]
            qs = _q_suffix(cores)
            p = jnp.ones((1, 1), jnp.float32)
            for m in range(nmodes):
                b = ttcore_ref(idx, val, cores, m, shape[m])
                w = _solve_core(jnp.kron(p, qs[m]), b)
                cores[m] = matrix_to_core(w, *pairs[m])
                facs[m] = (
                    jnp.zeros((prows[m], rps[m]), w.dtype)
                    .at[: shape[m], : lr[m]]
                    .set(w)
                )
                p = _p_next(p, cores[m])
            inner = tt_inner(idx, val, cores)
            resid_sq = jnp.maximum(norm_x_sq + p[0, 0] - 2.0 * inner, 0.0)
            fit = 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)
            return tuple(facs), None, fit

        jitted = jax.jit(sweep)
        return lambda facs, *args, it: jitted(facs, *args)


def make_planned_tt(
    st: SparseTensor,
    tt_ranks: int | Sequence[int],
    *,
    cfg: MemoryControllerConfig | None = None,
    auto_tune: bool | str = False,
    spec: TPUSpec | str = TPUSpec(),
) -> PlannedTT:
    """Build the full TT-ALS workspace: one tuned TT-core plan per output
    mode.

    With auto_tune=True each mode gets its own PMS-selected controller
    configuration scored for the TT kernel (two interface scratch chains in
    the VMEM model); otherwise `cfg` (or the default) is shared by every
    mode."""
    tr = _validated_tt_ranks(st, tt_ranks)
    ops = {
        m: make_planned_ttcore(
            st, m, tr, cfg=cfg, auto_tune=auto_tune, spec=spec
        )
        for m in range(st.nmodes)
    }
    return PlannedTT(ops=ops, shape=st.shape, tt_ranks=tr)


@f32_matmuls
def tt_als(
    st: SparseTensor,
    tt_ranks: int | Sequence[int],
    *,
    iters: int = 10,
    method: str = "pallas",
    init: str = "auto",
    seed: int = 0,
    tol: float | None = None,
    planned: "PlannedTT | None" = None,
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
) -> TTState:
    """Run sparse tensor-train ALS.

    tt_ranks: the N-1 interior bond ranks (an int broadcasts).
    method: 'pallas' — the planned TT-core memory-controller kernel: a
            `PlannedTT` workspace is built once (one remapped,
            device-resident BlockPlan per output mode) and reused for every
            iteration; 'pallas_sharded' — the distributed planned path
            (repro.dist.planned): per-mode balanced stream partitions,
            shard-local layouts, one jitted shard_map sweep per iteration
            with a single psum of the partial B_m per mode; 'reference' —
            the pure-jnp TT-core oracle.
    init:   'svd' — deterministic TT-SVD warm start (densifies; guarded to
            2^22 elements); 'random' — left-orthogonal random cores from
            `seed`; 'auto' — SVD when the dense guard allows, else random.
    planned / auto_tune / cfg: pallas-path knobs — pass a
            prebuilt `PlannedTT` (or `ShardedPlannedTT`) to reuse plans
            across calls, or let auto_tune run the TT-aware PMS per mode
            (worst-shard makespan for the sharded path).
            auto_tune="cached" persists/reuses the winners on disk; spec may
            be a TPUSpec, "default", or "measured" (repro.tune).
    jit_sweep: run each iteration as one jitted sweep (interface matrices
            stay device-resident, lane-padded, across iterations); False
            keeps the eager per-mode dispatch loop as the parity baseline
            ('pallas_sharded' is sweep-only and rejects jit_sweep=False).
    devices / dist: 'pallas_sharded' placement — a device count for the
            default 1-D `shard` mesh, or an explicit ShardingPlan.
    guards / checkpoint_every / checkpoint_path: the resilience surface of
            the planned drive loop (repro.resilience).  Planned jitted
            paths only.
    """
    tr = _validated_tt_ranks(st, tt_ranks)
    nmodes = st.nmodes
    pairs = _tt_bond_pairs(tr, nmodes)
    if init == "auto":
        init = "svd" if math.prod(st.shape) <= _TT_SVD_DENSE_LIMIT else "random"
    if init not in ("svd", "random"):
        raise ValueError(
            f"unknown init {init!r}: expected 'auto', 'svd' or 'random'"
        )
    with _trace.span("job.init"):
        if init == "svd":
            cores = tt_svd(st, tr)
        else:
            cores = init_tt_cores(jax.random.PRNGKey(seed), st.shape, tr)
        norm_x_sq = jnp.asarray(
            float(np.sum(st.values.astype(np.float64) ** 2)), jnp.float32)
    fits: list[float] = []

    check_planned_method(method, planned, devices, dist)
    check_drive_extras(method, jit_sweep, guards, checkpoint_every,
                       checkpoint_path)
    if method == "pallas_sharded":
        require_sharded_sweep(jit_sweep)
        from ..kernels.ops import ShardedPlannedTT, make_sharded_planned_tt

        if planned is None:
            planned = make_sharded_planned_tt(
                st, tr, dist=dist, devices=devices, cfg=cfg,
                auto_tune=auto_tune, spec=spec,
            )
        else:
            check_workspace(
                planned, ShardedPlannedTT, method,
                {"shape": st.shape, "tt_ranks": tr}, devices=devices,
            )
        mats = [core_to_matrix(c) for c in cores]
        mats, _, fits = planned.drive(
            mats, (norm_x_sq,), iters=iters, tol=tol, verbose=verbose,
            label="tt_als", guards=guards,
            checkpoint_every=checkpoint_every, checkpoint_path=checkpoint_path,
        )
        return TTState(
            cores=[matrix_to_core(w, *pairs[m]) for m, w in enumerate(mats)],
            fit_history=fits,
        )
    if method == "pallas":
        if planned is None:
            planned = make_planned_tt(
                st, tr, cfg=cfg, auto_tune=auto_tune, spec=spec,
            )
        else:
            check_workspace(
                planned, PlannedTT, method, {"shape": st.shape, "tt_ranks": tr}
            )
        if jit_sweep:
            # Fast path: interface matrices padded once, updated in padded
            # space by one jitted sweep per iteration; folded back to cores
            # only for the TTState.
            with _trace.span("job.upload"):
                idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
            mats = [core_to_matrix(c) for c in cores]
            mats, _, fits = planned.drive(
                mats, (idx, val, norm_x_sq), iters=iters, tol=tol,
                verbose=verbose, label="tt_als", guards=guards,
                checkpoint_every=checkpoint_every,
                checkpoint_path=checkpoint_path,
            )
            return TTState(
                cores=[matrix_to_core(w, *pairs[m]) for m, w in enumerate(mats)],
                fit_history=fits,
            )
    elif method != "reference":
        raise ValueError(f"unknown method {method!r}: expected 'pallas' or 'reference'")

    idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
    if method == "reference" and jit_sweep:
        cores_t = tuple(cores)
        for it in range(iters):
            cores_t, fit = _sweep_reference(
                cores_t, idx, val, norm_x_sq, shape=st.shape
            )
            if finish_iter(fits, fit, it, tol, verbose, "tt_als"):
                break
        return TTState(cores=list(cores_t), fit_history=fits)

    # Eager per-mode dispatch loop: jit_sweep=False (both methods).
    for it in range(iters):
        qs = _q_suffix(cores)
        p = jnp.ones((1, 1), jnp.float32)
        for m in range(nmodes):
            if method == "pallas":
                mats = [core_to_matrix(c) for c in cores]
                b = planned.ops[m].output(mats, st.shape[m])
            else:
                b = ttcore_ref(idx, val, cores, m, st.shape[m])
            w = _solve_core(jnp.kron(p, qs[m]), b)
            cores[m] = matrix_to_core(w, *pairs[m])
            p = _p_next(p, cores[m])
        if finish_iter(
            fits, tt_fit_value(idx, val, cores, norm_x_sq), it, tol, verbose, "tt_als"
        ):
            break
    return TTState(cores=cores, fit_history=fits)
