"""The `PlannedWorkspace` protocol: one shared implementation of everything a
planned decomposition workspace does that is NOT format-specific.

The paper's thesis is that the memory controller is *programmable* — one
remapped-COO data path serving many tensor kernels.  CP (MTTKRP), Tucker
(TTMc) and tensor-train (TT core update) all drive the same per-output-mode
BlockPlan layouts; what differs per format is only the per-mode contraction
and the factor-update math.  This module owns the shared layer:

  * rank padding + device-resident factor management (`pad_factors` /
    `unpad_factors` / `padded_rows` / `rank_pads`), parameterized by the one
    format-specific quantity — `lane_ranks`, each mode's true lane width
    (CP: R for every mode; Tucker: R_m; TT: rl_m * rr_m);
  * plan-per-mode amortization bookkeeping (`plan_bytes`, layout-byte
    accounting for both the single-device and shard-stacked layouts);
  * the lazily-compiled sweep cache (`sweep` builds `_build_sweep()` once);
  * `drive` — the host loop shared by every jitted path: pad once, one sweep
    per iteration, host-side tol early-exit, unpad at materialization;
  * the device-side plan arrays every kernel family consumes.

Format classes (`PlannedCPALS`, `PlannedTucker`, `PlannedTT` and their
sharded variants) subclass `PlannedWorkspace` / `ShardedWorkspace` and
provide only `lane_ranks`, `_geoms()` and `_build_sweep()` — the
format-specific sweep body IS the format.
"""
from __future__ import annotations

import re
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.loop import DecompositionDiverged, GuardState, finish_iter
from ..core.pms import kernel_fetch_bytes
from ..core.remap import BlockPlan
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .blocked import chunk_blocks
from .mttkrp_pallas import pad_factor, rank_padded

__all__ = [
    "PlannedWorkspace",
    "ShardedWorkspace",
    "planned_layout_bytes",
    "sharded_layout_bytes",
    "plan_stream",
    "sweep_scope",
]

# `<fmt>.m<n>.kernel`, `<fmt>.m<n>.update` or `<fmt>.fit`: the one pattern
# of the names `sweep_scope` makes.
_SCOPE_RE = re.compile(r"(?:cp|tucker|tt)\.(?:m\d+\.(?:kernel|update)|fit)")
_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$", re.M)
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')


def sweep_scope(fmt: str, part: str, mode: int | None = None):
    """The `jax.named_scope` of one part of a single-device sweep:
    `<fmt>.m<mode>.kernel` around mode `mode`'s kernel call,
    `<fmt>.m<mode>.update` around its update, `<fmt>.fit` around the fit.
    A scope adds op_name metadata to the compiled program and changes no
    operation."""
    return jax.named_scope(f"{fmt}.{part}" if mode is None else f"{fmt}.m{mode}.{part}")


def _scope_map(hlo_text: str) -> dict[str, str | None]:
    """{instruction name: sweep scope} of every instruction of a compiled
    module: the `sweep_scope` its op_name metadata passes through, None
    where there is none."""
    out = {}
    for name, rest in _INSTRUCTION_RE.findall(hlo_text):
        op_name = _OP_NAME_RE.search(rest)
        parts = op_name[1].split("/") if op_name else ()
        out[name] = next((p for p in parts if _SCOPE_RE.fullmatch(p)), None)
    return out


def plan_stream(plan: BlockPlan) -> tuple[np.ndarray, np.ndarray]:
    """Reconstruct a COO stream equivalent to a plan's remapped layout, for
    reference-sweep fallbacks whose drivers never kept the raw stream (Tucker's
    sweep takes no stream arguments).  Padding slots carry value 0.0 and
    in-bounds local coordinates, so they contribute nothing to any
    scatter/inner-product the reference kernels run."""
    blk = plan.blk
    cols: dict[int, np.ndarray] = {
        plan.mode: (
            np.repeat(plan.block_it.astype(np.int64), blk) * plan.tile_i
            + plan.iloc.astype(np.int64)
        )
    }
    for n, im in enumerate(plan.in_modes):
        cols[im] = (
            np.repeat(plan.block_in[n].astype(np.int64), blk) * plan.in_tiles[n]
            + plan.in_locs[n].astype(np.int64)
        )
    nmodes = 1 + plan.n_in
    idx = np.stack([cols[m] for m in range(nmodes)], axis=1).astype(np.int32)
    return idx, np.asarray(plan.vals)


@jax.jit
def _finite_flag(facs):
    return jnp.stack([jnp.isfinite(f).all() for f in facs]).all()


def _factors_finite(facs) -> bool:
    """One host sync for the whole factor tuple (guards' cadence check).
    The reduction is jitted: eager per-factor dispatch costs more than the
    check itself on the drive loop's hot path."""
    return bool(_finite_flag(tuple(facs)))


def _jitter_factors(factors, attempt: int):
    """Deterministic restart re-init: the original factors plus a small
    relative jitter (1e-4 of each factor's scale), keyed by the attempt
    number.  Staying near the original init keeps the restarted trajectory's
    final fit within the clean run's convergence basin — a fresh random seed
    would land on a different seed-dependent fit entirely."""
    key = jax.random.PRNGKey(0x5EED + attempt)
    out = []
    for i, f in enumerate(factors):
        k = jax.random.fold_in(key, i)
        scale = 1e-4 * (jnp.std(f) + 1e-12)
        out.append(f + scale * jax.random.normal(k, f.shape, f.dtype))
    return out


def _plan_device_arrays(plan: BlockPlan) -> tuple:
    """Move a BlockPlan's layout to device as the kernels' leading arguments:
    the per-block tile-id streams, then the (nblocks, 1, blk) stream arrays
    (values, output local indices, input local indices)."""
    nb, blk = plan.nblocks, plan.blk
    stream = lambda a: jnp.asarray(a).reshape(nb, 1, blk)
    return (
        jnp.asarray(plan.block_it),
        tuple(jnp.asarray(t) for t in plan.block_in),
        stream(plan.vals),
        stream(plan.iloc),
        tuple(stream(l) for l in plan.in_locs),
    )


def planned_layout_bytes(ops: dict[int, Any]) -> int:
    """HBM held by a per-mode plan family's remapped layouts (the 'copies'
    space/time trade, Sec. 3).  Element widths come from each mode's Remapper
    configuration; identical for every kernel family — the layout is shared."""
    total = 0
    for op in ops.values():
        p, r = op.plan, op.cfg.remapper
        slots = p.vals.shape[0]
        total += slots * (r.value_bytes + (1 + p.n_in) * r.index_bytes)
        total += p.nblocks * (1 + p.n_in) * r.index_bytes
    return total


def sharded_layout_bytes(stacks: dict[int, Any], cfgs: dict[int, Any]) -> int:
    """HBM held by a per-mode shard-stack family, summed over every device
    (the distributed 'copies' trade: N layouts per shard) — the sharded
    analogue of `planned_layout_bytes`.  Counts the padded stack width, i.e.
    what is actually resident."""
    total = 0
    for m, s in stacks.items():
        r = cfgs[m].remapper
        slots = s.nshards * s.nblocks * s.blk
        total += slots * (r.value_bytes + (1 + s.n_in) * r.index_bytes)
        total += s.nshards * s.nblocks * (1 + s.n_in) * r.index_bytes
    return total


def _padded_rows_from(geoms: dict[int, Any], nmodes: int) -> tuple[int, ...]:
    """Shared row-padding rule over any per-mode layout family exposing
    BlockPlan geometry (`out_rows` / `in_modes` / `in_rows`): single-device
    plans and sharded `_ShardStack`s use identical padding, so factors can
    move between the two paths without re-padding."""
    rows = []
    for m in range(nmodes):
        r = geoms[m].out_rows
        for g in geoms.values():
            for n, im in enumerate(g.in_modes):
                if im == m:
                    r = max(r, g.in_rows[n])
        rows.append(r)
    return tuple(rows)


class PlannedWorkspace:
    """Base protocol of every planned decomposition workspace.

    Subclass contract (the entire per-format surface):
      * a `shape` attribute — the true tensor shape;
      * `lane_ranks` — each mode's true lane width (the factor's column
        count: CP R, Tucker R_m, TT rl_m*rr_m);
      * `_geoms()` — the per-mode layout family (BlockPlans or _ShardStacks)
        for the shared row-padding rule;
      * `_layout_bytes()` — HBM held by the layouts;
      * `_build_sweep()` — compile the format's jitted sweep; its result must
        accept rank-padded factors first and return
        (new padded factors, aux, fit).

    The base provides the padded-space residency contract shared by every
    format: `pad_factors` pads each mode ONCE for the whole decomposition (to
    the maximum row padding any plan needs, lanes to `rank_padded`); sweeps
    update factors in padded space, keeping padding rows/lanes exactly zero
    so grams/fits in padded space match the true-shape computation bit for
    bit; `unpad_factors` slices back only at materialization.
    """

    _sweep_fn = None  # instance attribute on first `sweep` call
    _fallback_fn = None  # instance attribute on first fallback degradation
    fetch_bytes: dict[int, int] | None = None  # per mode, set once at build
    predicted_sweep_s: float | None = None  # PMS sweep seconds, set once at build

    def __post_init__(self):
        """Counted once per workspace, from its built plans: the HBM bytes
        each mode's kernel call moves (`kernel.fetch_bytes{mode=}`), its
        grid steps (`kernel.grid_steps{mode=}`, the plan's blocks) and the
        PMS-predicted sweep seconds that traced `sweep` spans carry."""
        pads = self.rank_pads
        self.fetch_bytes = {}
        for m, op in self.ops.items():
            p = op.plan
            self.fetch_bytes[m] = kernel_fetch_bytes(
                p, tuple(pads[im] for im in p.in_modes), rank_padded(op.out_cols),
                op.cfg.remapper, chunk_blocks(1 + p.n_in),
            )
            _metrics.gauge("kernel.fetch_bytes", mode=m).set(self.fetch_bytes[m])
            _metrics.gauge("kernel.grid_steps", mode=m).set(p.nblocks)
        self.predicted_sweep_s = float(
            sum(e.t_total for e in self.pms_estimates().values())
        )

    @property
    def nmodes(self) -> int:
        return len(self.shape)

    @property
    def lane_ranks(self) -> tuple[int, ...]:
        """Per-mode true lane width of each factor (format-specific)."""
        raise NotImplementedError

    @property
    def rank_pads(self) -> tuple[int, ...]:
        """Per-mode lane padding: each factor padded to its own width."""
        return tuple(rank_padded(r) for r in self.lane_ranks)

    @property
    def padded_rows(self) -> tuple[int, ...]:
        """Per-mode device-resident row padding (see `_padded_rows_from`)."""
        return _padded_rows_from(self._geoms(), self.nmodes)

    def _geoms(self) -> dict[int, Any]:
        raise NotImplementedError

    def _layout_bytes(self) -> int:
        raise NotImplementedError

    def _build_sweep(self):
        raise NotImplementedError

    def _sweep_variants(self) -> tuple[dict, ...]:
        """Keyword arguments of each distinct program `drive` runs; formats
        whose sweep retraces on a static argument (CP's `first`) override
        this."""
        return ({},)

    def pad_factors(self, factors: Sequence[jax.Array]) -> tuple[jax.Array, ...]:
        """One pad per mode for the whole decomposition (not N x iters)."""
        return tuple(
            pad_factor(f, rows, rp)
            for f, rows, rp in zip(factors, self.padded_rows, self.rank_pads)
        )

    def unpad_factors(self, padded: Sequence[jax.Array]) -> list[jax.Array]:
        return [
            f[:s, :r] for f, s, r in zip(padded, self.shape, self.lane_ranks)
        ]

    def plan_bytes(self) -> int:
        """HBM held by the per-mode layouts (the 'copies' trade, Sec. 3)."""
        return self._layout_bytes()

    def sweep(self, facs, *args, **kwargs):
        """One jitted iteration in padded space.

        `facs` is the factor tuple in PADDED space — one (padded_rows[m],
        rank_pads[m]) array per mode, as produced by `pad_factors` or a
        previous `sweep` call.  Invariant: padding rows and lanes are exactly
        zero on entry and are kept exactly zero on exit.  Returns (new padded
        factors, aux, fit), all device-resident — feeding the returned
        factors straight into the next call incurs zero host transfers and
        zero re-padding.  The compiled sweep is built lazily on first use and
        cached for the workspace's lifetime."""
        return self._jitted_sweep()(*self._sweep_operands(facs, args), **kwargs)

    def lower_sweep(self, facs, *args, **kwargs) -> jax.stages.Lowered:
        """The sweep lowered for these operands (the `sweep` contract).
        `.compile()` gives the program `sweep` runs: its compile time and
        its HLO text, where a compiled Pallas kernel is a `tpu_custom_call`."""
        return self._jitted_sweep().lower(*self._sweep_operands(facs, args), **kwargs)

    def sweep_scopes(self, *args) -> list[dict]:
        """For each compiled program `drive` runs (CP: the first and the
        steady sweep), `{"module": HLO module name, "scopes": {instruction
        name: sweep scope or None}}` over every instruction of its compiled
        text.  A profiler's device op events carry their instruction name
        but no metadata, and CP's two programs share one module name: an
        execution's instruction names tell which program ran, and this map
        names each op's scope.  `args` are the sweep's arguments after the
        factors (arrays or `jax.ShapeDtypeStruct`s)."""
        facs = tuple(
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32)
            for rows, lanes in zip(self.padded_rows, self.rank_pads)
        )
        out = []
        for kwargs in self._sweep_variants():
            text = self.lower_sweep(facs, *args, **kwargs).compile().as_text()
            out.append({"module": text.split(None, 2)[1].rstrip(","),
                        "scopes": _scope_map(text)})
        return out

    def _jitted_sweep(self):
        if self._sweep_fn is None:
            self._sweep_fn = self._build_sweep()
        return self._sweep_fn

    def _sweep_operands(self, facs, args) -> tuple:
        """The per-mode layouts lead the sweep's operands: arrays a jitted
        function closes over are embedded in its program as constants,
        which at a real tensor's size is gigabytes of program."""
        return ({m: op.layout for m, op in self.ops.items()}, facs, *args)

    def _sweep_call(self, facs, *args, it: int):
        """`drive`'s per-iteration hook; formats whose sweep takes the
        iteration count (CP's `first` retrace) override this."""
        return self.sweep(facs, *args)

    def _build_fallback_sweep(self):
        """Compile the format's REFERENCE sweep as a drive-compatible callable
        `(facs, *args, it=...) -> (facs, aux, fit)` operating on the same
        padded factors — the graceful-degradation target of the "fallback"
        guard policy (pallas -> reference mid-run without re-padding).  Return
        None if the workspace has no reference path (sharded workspaces)."""
        return None

    def _fallback_sweep(self):
        if self._fallback_fn is None:
            self._fallback_fn = self._build_fallback_sweep()
        return self._fallback_fn

    def vmem_model_bytes(self) -> int:
        """Peak VMEM working set the PMS model predicts for this workspace's
        kernel family — part of the admission total (`repro.resilience.admit`).
        Format classes supply the per-kind formula; the base contributes 0."""
        return 0

    def drive(self, factors, args=(), *, iters: int, tol=None,
              verbose: bool = False, label: str = "decompose",
              guards=None, reinit=None,
              checkpoint_every: int | None = None, checkpoint_path=None):
        """The shared host loop of every jitted planned path: pad once, one
        compiled sweep per iteration, host-side tol early-exit on the fit
        scalar (the only device->host sync), unpad at materialization.
        Returns (true-shape factors, aux from the last sweep, fit history).

        Resilience surface (repro.resilience):
          * guards — a `GuardConfig`; each iteration's fit scalar feeds the
            divergence tracker for free, plus an optional factor-finiteness
            check every `check_factors_every` iterations.  On detection the
            policy either raises `DecompositionDiverged`, restarts from
            jittered re-init (`reinit(attempt)` if given, else the original
            factors + deterministic 1e-4 jitter; at most `max_restarts`
            times), or degrades to the format's reference sweep reusing the
            last good padded factors.
          * checkpoint_every/checkpoint_path — persist (padded factors, fit
            history) every k iterations via `train.checkpoint`; when the
            directory already holds a checkpoint, `drive` resumes from it
            bit-for-bit instead of starting over.
        """
        gs = GuardState(guards) if guards is not None else None
        fits: list[float] = []
        with _trace.span("drive.pad", label=label):
            facs = self.pad_factors(factors)
        aux = None
        sweep_call = self._sweep_call
        fb_active = False

        ckpt = None
        start = 0
        if checkpoint_path is not None:
            from ..train.checkpoint import CheckpointManager

            if checkpoint_every is None:
                checkpoint_every = 1
            elif checkpoint_every < 1:
                raise ValueError("checkpoint_every must be >= 1")
            ckpt = CheckpointManager(checkpoint_path, keep=2)
            step = ckpt.latest_step()
            if step is not None:
                step, tree = ckpt.restore(step)
                saved = tuple(tree["facs"])
                want = tuple(f.shape for f in facs)
                got = tuple(tuple(f.shape) for f in saved)
                # Padded shapes alone cannot distinguish ranks below the
                # lane width (both pad to the same lanes), so the true
                # lane_ranks ride along in the checkpoint.
                saved_lr = tuple(int(r) for r in np.asarray(
                    tree.get("lane_ranks", self.lane_ranks)).ravel())
                if got != want or saved_lr != tuple(self.lane_ranks):
                    raise ValueError(
                        f"checkpoint at {checkpoint_path!r} holds padded "
                        f"factors of shapes {got} (lane ranks {saved_lr}) "
                        f"but this workspace pads to {want} (lane ranks "
                        f"{tuple(self.lane_ranks)}); it was written by a "
                        f"different tensor/rank/workspace"
                    )
                facs = tuple(jnp.asarray(f) for f in saved)
                fits = [float(f) for f in np.asarray(tree["fits"]).ravel()]
                start = int(step) + 1
                _metrics.counter("resilience.resumes", label=label).inc()
                _trace.event("checkpoint_resume", label=label, step=int(step))
                if verbose:
                    print(f"[{label}] resumed from checkpoint step {step} "
                          f"({len(fits)} fits recorded)")
        elif checkpoint_every is not None:
            raise ValueError("checkpoint_every requires checkpoint_path")

        # Per-iteration observability (docs/observability.md): metric
        # handles are resolved once so the hot loop pays no registry lookup;
        # the per-sweep span carries the PMS-predicted sweep time when the
        # format has one, which is what `obs.calibrate.join_trace` joins
        # achieved_pct from.
        m_iter = _metrics.histogram("drive.iter_seconds", label=label)
        m_count = _metrics.counter("drive.iterations", label=label)
        predicted_s = self.predicted_sweep_s

        it = start
        prev_facs = None  # one-step history: the fallback rebase target
        with _trace.span("drive", label=label, iters=iters, start=start):
            while it < iters:
                t_sweep = time.perf_counter()
                with _trace.span("sweep", label=label, it=it,
                                 predicted_s=predicted_s):
                    new_facs, aux, fit = sweep_call(facs, *args, it=it)
                    fit = float(fit)
                m_iter.observe(time.perf_counter() - t_sweep)
                m_count.inc()
                reason = None
                if gs is not None:
                    reason = gs.observe_fit(fit)
                    if (reason is None and gs.cfg.check_factors_every > 0
                            and (it + 1) % gs.cfg.check_factors_every == 0
                            and not _factors_finite(new_facs)):
                        reason = "non-finite factor entries"
                if reason is not None:
                    policy = gs.cfg.policy
                    if policy == "restart" and gs.restarts < gs.cfg.max_restarts:
                        gs.restarts += 1
                        _metrics.counter("resilience.restarts", label=label).inc()
                        _trace.event("guard_restart", label=label, it=it,
                                     reason=reason, attempt=gs.restarts)
                        if verbose:
                            print(f"[{label}] iter {it:3d} {reason}; restart "
                                  f"{gs.restarts}/{gs.cfg.max_restarts} with "
                                  f"jittered re-init")
                        base = (reinit(gs.restarts) if reinit is not None
                                else _jitter_factors(factors, gs.restarts))
                        facs = self.pad_factors(base)
                        fits = []
                        gs.reset()
                        it = 0
                        continue
                    if policy == "fallback" and not fb_active:
                        fb = self._fallback_sweep()
                        if fb is not None:
                            fb_active = True
                            sweep_call = fb
                            gs.reset()
                            _metrics.counter(
                                "resilience.fallbacks", label=label).inc()
                            _trace.event("guard_fallback", label=label,
                                         it=it, reason=reason)
                            # The current iterate may itself be corrupted (its
                            # fit looked fine when it was accepted, e.g. a factor
                            # poisoned after the fit was computed): rebase onto
                            # the previous accepted iterate and redo the tainted
                            # iteration in place, so the run loses no sweeps.
                            if not _factors_finite(facs) and prev_facs is not None:
                                facs = prev_facs
                                if fits:
                                    fits.pop()
                                it -= 1
                            if verbose:
                                print(f"[{label}] iter {it:3d} {reason}; "
                                      f"degrading to the reference sweep on the "
                                      f"last good factors")
                            continue  # retry this iteration on the good iterate
                        reason += " (no reference fallback sweep for this workspace)"
                    elif policy == "fallback":
                        reason += " (already running the reference fallback)"
                    elif policy == "restart":
                        reason += (f" (restart budget of {gs.cfg.max_restarts} "
                                   f"exhausted)")
                    _metrics.counter("resilience.diverged", label=label).inc()
                    _trace.event("guard_diverged", label=label, it=it,
                                 reason=reason)
                    raise DecompositionDiverged(label, it, reason, fits + [fit])
                prev_facs, facs = facs, new_facs
                stop = finish_iter(fits, fit, it, tol, verbose, label)
                if ckpt is not None and (
                    stop or it + 1 == iters or (it + 1) % checkpoint_every == 0
                ):
                    with _trace.span("checkpoint_save", label=label, it=it):
                        ckpt.save(
                            it, {"facs": tuple(facs),
                                 "fits": np.asarray(fits, np.float64),
                                 "lane_ranks": np.asarray(self.lane_ranks, np.int64)}
                        )
                if stop:
                    break
                it += 1
        with _trace.span("drive.unpad", label=label):
            return self.unpad_factors(facs), aux, fits


class ShardedWorkspace(PlannedWorkspace):
    """Base of the distributed workspaces (repro.dist.planned): the same
    protocol over per-mode `_ShardStack`s — shard d of mode m's stack holds
    the remapped, device-resident layout of shard d's slice of the stream —
    with the sweep running as one jitted shard_map.  Subclasses additionally
    carry `stacks` / `dist` / `cfgs`; `_stream_args()` supplies the
    shard-stacked fit stream for formats whose fit walks the non-zeros."""

    def __post_init__(self):
        """The sharded sweeps keep no fetch or step count and no PMS
        prediction."""

    @property
    def nshards(self) -> int:
        return self.dist.dp_size()

    def _geoms(self) -> dict[int, Any]:
        return self.stacks

    def _layout_bytes(self) -> int:
        return sharded_layout_bytes(self.stacks, self.cfgs)

    def _stream_args(self) -> tuple:
        return ()

    def _sweep_operands(self, facs, args) -> tuple:
        """The `PlannedWorkspace.sweep` contract minus any stream arguments:
        each shard's slice already lives on its device."""
        arrs = {m: self.stacks[m].tree() for m in range(self.nmodes)}
        return (arrs, *self._stream_args(), facs, *args)
