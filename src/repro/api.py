"""Unified tensor-network decomposition facade.

One entry point for all three formats on the programmable-memory-controller
substrate:

    from repro.api import decompose

    cp  = decompose(st, rank=8)                          # CP-ALS
    tk  = decompose(st, rank=(4, 4, 4), format="tucker") # Tucker HOOI
    tt  = decompose(st, rank=(4, 3), format="tt")        # TT-ALS

Every format runs the same stack underneath: the Tensor Remapper builds one
BlockPlan per output mode, a `PlannedWorkspace` (kernels/workspace.py) keeps
lane-padded factors device-resident and drives the fully-jitted sweep with
host-side tol early-exit, and the format supplies only its sweep body
(MTTKRP + normal solve for CP, TTMc + Gram eigh for Tucker, TT-core +
kron(P, Q) solve for TT).  `method="pallas_sharded"` routes through the
distributed planned path (repro.dist.planned) for any format; `planned=`
accepts the format's prebuilt workspace for plan reuse across calls.

This module deliberately holds no algorithm logic — it normalizes the rank
argument per format and dispatches to `cp_als` / `tucker_hooi` / `tt_als`,
whose keyword surfaces are already aligned."""
from __future__ import annotations

from typing import Sequence

from .core.coo import SparseTensor
from .obs import trace as _trace

__all__ = ["decompose"]

_FORMATS = ("cp", "tucker", "tt")


def _normalized_rank(format: str, rank, nmodes: int):
    """Per-format rank normalization: CP takes a single int; Tucker an
    N-tuple (an int broadcasts to every mode); TT the N-1 interior bond
    ranks (an int broadcasts to every bond).  Detailed range validation
    stays with each format's driver."""
    if format == "cp":
        if not isinstance(rank, int):
            raise ValueError(
                f"format='cp' takes a single integer rank, got {rank!r}"
            )
        return rank
    if format == "tucker":
        if isinstance(rank, int):
            return (rank,) * nmodes
        return tuple(int(r) for r in rank)
    if isinstance(rank, int):
        return (rank,) * (nmodes - 1)
    return tuple(int(r) for r in rank)


def _lane_ranks(format: str, r, nmodes: int) -> tuple[int, ...]:
    """Per-mode factor lane widths (the `PlannedWorkspace.lane_ranks` rule)
    without building a workspace — sizes the reference rung of the admission
    ladder."""
    if format == "cp":
        return (r,) * nmodes
    if format == "tucker":
        return tuple(r)
    bounds = (1,) + tuple(r) + (1,)
    return tuple(bounds[m] * bounds[m + 1] for m in range(nmodes))


def _admitted(st, r, *, format, method, planned, hbm_budget, auto_tune, cfg,
              verbose):
    """`hbm_budget=` handling: admit a prebuilt workspace as-is, or run the
    graceful-degradation ladder (`repro.resilience.plan_with_budget`) over
    freshly built workspaces — stepping down the DMA block size, then the
    reference path, then `AdmissionError`.  Returns the (possibly built)
    workspace and the (possibly degraded) method."""
    from .resilience import admit, plan_with_budget, reference_footprint_bytes

    reference_method = "approach1" if format == "cp" else "reference"
    if method not in ("pallas", reference_method, "approach2"):
        raise ValueError(
            f"hbm_budget applies to method='pallas' and the reference "
            f"methods, got method={method!r}"
        )
    ref_bytes = reference_footprint_bytes(st, _lane_ranks(format, r, st.nmodes))
    if method != "pallas":
        if ref_bytes > hbm_budget:
            from .resilience import AdmissionError

            raise AdmissionError(hbm_budget, [], ref_bytes)
        return planned, method
    if planned is not None:
        admit(planned, hbm_budget)
        return planned, method
    if auto_tune:
        raise ValueError(
            "hbm_budget's degradation ladder steps the controller config "
            "explicitly; it is incompatible with auto_tune=True"
        )
    if format == "cp":
        from .kernels.ops import make_planned_cp_als as build_ws
    elif format == "tucker":
        from .tucker.hooi import make_planned_tucker as build_ws
    else:
        from .tt.als import make_planned_tt as build_ws
    ws, decision = plan_with_budget(
        lambda c: build_ws(st, r, cfg=c),
        hbm_budget, cfg=cfg, reference_bytes=ref_bytes,
    )
    if verbose:
        rungs = ", ".join(
            f"blk={a['blk']}:{a['total_bytes']:,}B" for a in decision["ladder"]
        )
        print(f"[admission] {decision['admitted']} admitted under "
              f"{hbm_budget:,}B (ladder: {rungs or 'none'})")
    if ws is None:
        return None, reference_method
    return ws, method


def decompose(
    st: SparseTensor,
    rank: int | Sequence[int],
    *,
    format: str = "cp",
    method: str = "pallas",
    iters: int = 10,
    seed: int = 0,
    tol: float | None = None,
    planned=None,
    auto_tune: bool | str = False,
    spec="default",
    cfg=None,
    jit_sweep: bool = True,
    devices: int | None = None,
    dist=None,
    verbose: bool = False,
    guards=None,
    hbm_budget: int | None = None,
    checkpoint_every: int | None = None,
    checkpoint_path=None,
    trace=None,
    **format_kwargs,
):
    """Decompose a sparse tensor on the programmable memory controller.

    Args:
      st: host-side COO tensor (>= 3 modes).
      rank: CP rank (int), Tucker core ranks (N-tuple; int broadcasts), or
        TT interior bond ranks (N-1 tuple; int broadcasts) — selected by
        `format`.
      format: 'cp' (CP-ALS), 'tucker' (HOOI) or 'tt' (TT-ALS).
      method: 'pallas' — the planned memory-controller kernel (one remapped,
        device-resident BlockPlan per output mode, built once and reused
        every iteration); 'pallas_sharded' — the distributed planned path
        (one jitted shard_map sweep per iteration, a single psum per mode);
        'reference' — the format's pure-jnp oracle (Tucker/TT; for CP the
        eager compute-pattern methods 'approach1'/'approach2' play that
        role).
      iters / seed / tol / verbose: iteration count, init seed, host-side
        relative-fit early-exit, per-iteration fit printing.
      planned: a prebuilt format workspace (`PlannedCPALS`, `PlannedTucker`,
        `PlannedTT`, or their Sharded* variants) to reuse plans across
        calls; type-checked against `format`/`method`.
      auto_tune / cfg: pallas-path knobs — per-mode PMS tuning, explicit
        controller config.  The kernels run compiled on a TPU and in
        interpret mode elsewhere; the platform decides (`repro.platform`).
        auto_tune accepts False, True, or "cached": "cached" serves each
        mode's persisted PMS winner from the on-disk autotune cache
        (repro.tune.cache; `$REPRO_AUTOTUNE_DIR`), skipping the config
        sweep entirely on a warm hit — identical factors, zero search
        configs evaluated — and searching + writing back on a miss.
      spec: PMS hardware constants for the search — a
        `repro.core.memctrl.TPUSpec`, "default" (datasheet guesses), or
        "measured" (this backend's calibrated spec from the autotune cache;
        auto-calibrates on first use — see docs/autotune.md).
      jit_sweep: fully-jitted per-iteration sweep (the default); False keeps
        each format's eager per-mode dispatch loop as the parity baseline.
      devices / dist: 'pallas_sharded' placement.
      guards: a `repro.resilience.GuardConfig` — numerical guards in the
        planned drive loop (non-finite fit, sustained fit regression,
        factor finiteness on cadence) with raise/restart/fallback recovery.
      hbm_budget: admission control (method='pallas' and the reference
        methods): the workspace's resident footprint (`plan_bytes()` +
        padded factors + the PMS VMEM model) must fit this many bytes.
        Over budget, the degradation ladder halves the DMA block size down
        to a floor, then drops to the reference path, and only then raises
        `repro.resilience.AdmissionError`.  Incompatible with a prebuilt
        `planned=` (which is admitted as-is, no ladder) and with
        auto_tune=True.
      checkpoint_every / checkpoint_path: persist padded factors + fit
        history every k iterations via `train.checkpoint`; a populated
        checkpoint directory resumes the sweep bit-for-bit.
      trace: observability tracing for this call (docs/observability.md):
        True collects spans into a fresh in-memory `repro.obs.Tracer`; a
        path collects AND exports them as JSONL on exit; an existing
        `Tracer` appends to it; None/False leaves the process-global state
        alone (so `REPRO_TRACE=1` still applies).  Restores the previous
        tracer when the call returns.
      **format_kwargs: forwarded to the format driver (e.g. TT's
        `init='svd'|'random'|'auto'`, CP's `layout=` / `mttkrp_fn=`).

    Returns:
      The format's state object — `CPState(factors, lam, fit_history)`,
      `TuckerState(factors, core, fit_history)` or
      `TTState(cores, fit_history)`; all carry `fit_history`.
    """
    if format not in _FORMATS:
        raise ValueError(
            f"unknown format {format!r}: expected 'cp', 'tucker' or 'tt'"
        )
    if auto_tune not in (False, True, "cached"):
        raise ValueError(
            f"auto_tune must be False, True or 'cached', got {auto_tune!r}"
        )
    r = _normalized_rank(format, rank, st.nmodes)
    with _trace.tracing(trace), _trace.span(
        "decompose", format=format, method=method,
        shape=list(st.shape), nnz=st.nnz, iters=iters,
    ):
        if hbm_budget is not None:
            planned, method = _admitted(
                st, r, format=format, method=method, planned=planned,
                hbm_budget=hbm_budget, auto_tune=auto_tune, cfg=cfg,
                verbose=verbose,
            )
        common = dict(
            iters=iters, method=method, seed=seed, tol=tol, planned=planned,
            auto_tune=auto_tune, spec=spec, cfg=cfg,
            jit_sweep=jit_sweep, devices=devices, dist=dist, verbose=verbose,
            guards=guards, checkpoint_every=checkpoint_every,
            checkpoint_path=checkpoint_path,
            **format_kwargs,
        )
        if format == "cp":
            from .core.cp_als import cp_als

            return cp_als(st, r, **common)
        if format == "tucker":
            from .tucker.hooi import tucker_hooi

            return tucker_hooi(st, r, **common)
        from .tt.als import tt_als

        return tt_als(st, r, **common)
