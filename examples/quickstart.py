"""Quickstart: decompose a synthetic FROSTT-like sparse tensor on the
memory-controller-planned Pallas kernels — every format the substrate serves
runs from this one entry point, through the unified `decompose()` facade
(repro/api.py):

  * --algo cp      (default)  CP-ALS on the planned MTTKRP kernel:
    `decompose(st, rank, format="cp")` builds a `PlannedCPALS` workspace
    (one remapped, device-resident BlockPlan per output mode, paper Alg. 5)
    once and reuses it for every ALS iteration (paper Alg. 1).
  * --algo tucker             Sparse Tucker (HOOI) on the planned TTM-chain
    kernel: `decompose(format="tucker")` drives the same per-mode BlockPlan
    layouts through the Kronecker-chain kernel — the controller is
    programmable, not CP-specific.
  * --algo tt                 Tensor-train ALS on the planned TT-core kernel:
    `decompose(format="tt")` drives the same layouts through the
    Kronecker-of-two-interfaces kernel — the third format on the substrate.
  * --devices N               Distribute any algorithm over N devices
    (`method="pallas_sharded"`, repro.dist.planned): the stream is
    partitioned into balanced output-tile ranges per mode, each shard's
    remapped layout is device-local, and every iteration is one shard_map
    sweep with a single psum per mode.  On CPU this forces an N-device host
    platform via XLA_FLAGS, which must happen BEFORE jax initializes — hence
    the deferred imports below.

  PYTHONPATH=src python examples/quickstart.py [--algo {cp,tucker,tt}]
                                               [--fast] [--devices N]
                                               [--trace PATH]
                                               [--auto-tune {off,on,cached}]

  --trace PATH exports an observability trace of the headline decompose()
  call as JSONL (repro.obs; summarize with scripts/trace_report.py, convert
  with --chrome for chrome://tracing).  REPRO_TRACE=1 (or =PATH) instead
  enables process-global tracing for everything this script runs.
  --auto-tune cached persists each mode's PMS winner in the on-disk autotune
  cache ($REPRO_AUTOTUNE_DIR or ~/.cache/repro-autotune; docs/autotune.md),
  so a rerun skips the config sweep entirely.
"""
import argparse
import os
import time


def _print_pms(best):
    for e in best:
        c, d = e.cfg.cache, e.cfg.dma
        print(f"PMS: tiles=({c.tile_i},{c.tile_j},{c.tile_k}) blk={d.blk} "
              f"-> t={e.t_total*1e6:.1f}us [{e.bottleneck}-bound] vmem={e.vmem_bytes/2**20:.0f}MiB")


def run_cp(st, fast: bool, devices: int, trace=None, auto_tune=False):
    from repro.api import decompose
    from repro.core.coo import frostt_like
    from repro.core.hypergraph import approach1_traffic, approach2_traffic, remap_overhead
    from repro.core.pms import search
    from repro.kernels.ops import make_planned_cp_als

    rank = 16
    # The paper's Table 1: why Approach 1 (output-direction) wins
    t1 = approach1_traffic(st, 0, rank)
    t2 = approach2_traffic(st, 0, rank)
    print(f"traffic (elements): approach1={t1.total_elems:,} approach2={t2.total_elems:,} "
          f"(x{t2.total_elems/t1.total_elems:.2f}); remap overhead={remap_overhead(st, 0, rank):.2%}")

    # PMS (Sec 5.3): pick the memory-controller configuration for MTTKRP
    _print_pms(search(st, 0, rank, top_k=3))

    # CP-ALS entirely on the planned Pallas kernel (compiled on a TPU,
    # interpreted on the CPU):
    # plans are built once per mode and amortized over all iterations.
    small = frostt_like("tiny")
    # With --auto-tune the facade builds (or, for "cached", loads) each
    # mode's PMS-selected configuration itself — no prebuilt workspace.
    planned = None if auto_tune else make_planned_cp_als(small, 8)
    if planned is not None:
        print(f"planned workspace: {small.nmodes} mode plans, "
              f"{planned.plan_bytes()/2**20:.2f} MiB of remapped copies on HBM")

    iters = 2 if fast else 5
    t0 = time.time()
    state = decompose(small, 8, format="cp", iters=iters, planned=planned,
                      auto_tune=auto_tune, verbose=True, trace=trace)
    print(f"CP-ALS fit={state.fit_history[-1]:.4f} in {time.time()-t0:.1f}s "
          f"(PlannedCPALS)")

    if devices > 1:
        # The same loop distributed: per-mode balanced stream partitions,
        # shard-local BlockPlans, one psum of factor rows per mode.
        t0 = time.time()
        sh = decompose(small, 8, format="cp", iters=iters,
                       method="pallas_sharded", devices=devices, verbose=True)
        print(f"CP-ALS (sharded x{devices}) fit={sh.fit_history[-1]:.4f} in "
              f"{time.time()-t0:.1f}s (single-device fit "
              f"{state.fit_history[-1]:.4f} — must match)")
        assert abs(sh.fit_history[-1] - state.fit_history[-1]) < 1e-4

    # The same workspace drives higher-order tensors (Table 2 has 3–5 modes)
    if not fast:
        st4 = frostt_like("4d_small")
        s4 = decompose(st4, 8, format="cp", iters=2)
        print(f"4-mode CP-ALS fit={s4.fit_history[-1]:.4f} (N-mode kernel)")


def run_tucker(st, fast: bool, devices: int, trace=None, auto_tune=False):
    from repro.api import decompose
    from repro.core.coo import frostt_like
    from repro.core.pms import search
    from repro.tucker import make_planned_tucker

    core_ranks = (8, 8, 8)
    # PMS scored for the TTM-chain kernel: the core-tensor tile (Kronecker
    # width prod(R_m) lanes) changes both the VMEM fit and the roofline.
    _print_pms(search(st, 0, 16, kernel="ttmc", core_ranks=core_ranks, top_k=3))

    # HOOI entirely on the planned TTMc kernel — the SAME BlockPlan layouts
    # MTTKRP uses, built once per mode and amortized over all iterations.
    small = frostt_like("tiny")
    ranks_small = (4, 4, 4)
    planned = None if auto_tune else make_planned_tucker(small, ranks_small)
    if planned is not None:
        print(f"planned workspace: {small.nmodes} mode plans, "
              f"{planned.plan_bytes()/2**20:.2f} MiB of remapped copies on HBM")

    iters = 2 if fast else 5
    t0 = time.time()
    state = decompose(small, ranks_small, format="tucker", iters=iters,
                      planned=planned, auto_tune=auto_tune, verbose=True,
                      trace=trace)
    print(f"Tucker HOOI fit={state.fit_history[-1]:.4f} core={state.core.shape} "
          f"in {time.time()-t0:.1f}s (PlannedTucker)")

    if devices > 1:
        t0 = time.time()
        sh = decompose(small, ranks_small, format="tucker", iters=iters,
                       method="pallas_sharded", devices=devices, verbose=True)
        print(f"Tucker HOOI (sharded x{devices}) fit={sh.fit_history[-1]:.4f} in "
              f"{time.time()-t0:.1f}s (single-device fit "
              f"{state.fit_history[-1]:.4f} — must match)")
        assert abs(sh.fit_history[-1] - state.fit_history[-1]) < 1e-4

    if not fast:
        st4 = frostt_like("4d_small")
        s4 = decompose(st4, (3, 3, 3, 3), format="tucker", iters=2)
        print(f"4-mode Tucker fit={s4.fit_history[-1]:.4f} (N-mode TTMc kernel)")


def run_tt(st, fast: bool, devices: int, trace=None, auto_tune=False):
    from repro.api import decompose
    from repro.core.coo import frostt_like
    from repro.core.pms import search
    from repro.tt import make_planned_tt

    tt_ranks = (8, 8)
    # PMS scored for the TT-core kernel: the two-interface scratch and the
    # rank_padded(rl*rr) lane widths change the VMEM fit and the roofline.
    _print_pms(search(st, 0, 16, kernel="tt", core_ranks=tt_ranks, top_k=3))

    # TT-ALS entirely on the planned TT-core kernel — the SAME BlockPlan
    # layouts MTTKRP/TTMc use, built once per mode and amortized over all
    # iterations.
    small = frostt_like("tiny")
    ranks_small = (4, 4)
    planned = None if auto_tune else make_planned_tt(small, ranks_small)
    if planned is not None:
        print(f"planned workspace: {small.nmodes} mode plans, "
              f"{planned.plan_bytes()/2**20:.2f} MiB of remapped copies on HBM")

    iters = 2 if fast else 5
    t0 = time.time()
    state = decompose(small, ranks_small, format="tt", iters=iters,
                      planned=planned, auto_tune=auto_tune, verbose=True,
                      trace=trace)
    print(f"TT-ALS fit={state.fit_history[-1]:.4f} tt_ranks={state.tt_ranks} "
          f"in {time.time()-t0:.1f}s (PlannedTT)")

    if devices > 1:
        t0 = time.time()
        sh = decompose(small, ranks_small, format="tt", iters=iters,
                       method="pallas_sharded", devices=devices, verbose=True)
        print(f"TT-ALS (sharded x{devices}) fit={sh.fit_history[-1]:.4f} in "
              f"{time.time()-t0:.1f}s (single-device fit "
              f"{state.fit_history[-1]:.4f} — must match)")
        assert abs(sh.fit_history[-1] - state.fit_history[-1]) < 1e-4

    if not fast:
        st4 = frostt_like("4d_small")
        s4 = decompose(st4, (3, 3, 3), format="tt", iters=2)
        print(f"4-mode TT-ALS fit={s4.fit_history[-1]:.4f} (N-mode TT kernel)")


def main(fast: bool = False, algo: str = "cp", devices: int = 1,
         trace: str | None = None, auto_tune=False):
    from pathlib import Path

    import jax

    from repro.core.coo import frostt_like
    from repro.platform import enable_compile_cache

    enable_compile_cache(Path(__file__).resolve().parent.parent)

    if devices > 1 and jax.device_count() < devices:
        raise SystemExit(
            f"need {devices} devices but jax sees {jax.device_count()}; on "
            f"CPU run through `python examples/quickstart.py --devices "
            f"{devices}` (it sets XLA_FLAGS before jax initializes)"
        )
    # A sparse tensor shaped like the FROSTT repository's (paper Table 2)
    st = frostt_like("tiny" if fast else "small")
    print(f"tensor: shape={st.shape} nnz={st.nnz:,} density={st.density:.2e} "
          f"algo={algo} devices={devices}")
    if algo == "cp":
        run_cp(st, fast, devices, trace, auto_tune)
    elif algo == "tucker":
        run_tucker(st, fast, devices, trace, auto_tune)
    elif algo == "tt":
        run_tt(st, fast, devices, trace, auto_tune)
    else:
        raise ValueError(f"unknown algo {algo!r}: expected 'cp', 'tucker' or 'tt'")
    if trace:
        print(f"trace -> {trace} (summarize: python scripts/trace_report.py {trace})")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="CI smoke subset")
    ap.add_argument("--algo", choices=("cp", "tucker", "tt"), default="cp",
                    help="decomposition to run on the planned kernels")
    ap.add_argument("--devices", type=int, default=1,
                    help="run the sharded planned path over N devices "
                         "(forces an N-device CPU host platform if needed)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the headline decompose() call's obs trace "
                         "as JSONL to PATH (see scripts/trace_report.py)")
    ap.add_argument("--auto-tune", choices=("off", "on", "cached"),
                    default="off", dest="auto_tune",
                    help="PMS tuning for the headline decompose() call: "
                         "'on' searches every run; 'cached' persists/reuses "
                         "the winners on disk ($REPRO_AUTOTUNE_DIR, see "
                         "docs/autotune.md) — a warm cache skips the sweep")
    a = ap.parse_args()
    if a.devices > 1:
        # Must precede the first jax import: the host device count locks at
        # jax init.  Honor a pre-existing forced count only if it is large
        # enough — otherwise fail here with the actual conflict, not after
        # jax has locked the smaller count.
        import re

        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={a.devices}".strip()
            )
        elif int(m.group(1)) < a.devices:
            raise SystemExit(
                f"XLA_FLAGS already forces {m.group(1)} host devices but "
                f"--devices {a.devices} was requested; unset "
                f"xla_force_host_platform_device_count or raise it to "
                f">= {a.devices}"
            )
    main(fast=a.fast, algo=a.algo, devices=a.devices, trace=a.trace,
         auto_tune={"off": False, "on": True, "cached": "cached"}[a.auto_tune])
