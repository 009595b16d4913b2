"""Smoke run of the decomposition path on a TPU.

    python chip_smoke.py             # one chip: CP, Tucker and TT
    python chip_smoke.py --chips 4   # the sharded path on four chips

One chip.  Builds the `nell2_like` tensor from a fixed seed: the mode lengths
of FROSTT's nell-2 (12,092 x 9,184 x 28,818) with 2 M of its ~77 M
nonzeros, cut because the BlockPlan pads every (output tile, input tiles)
group to a whole block (about 2 GB of layouts per format at 2 M nonzeros).
Then, for CP (rank 16), Tucker (ranks 8, 8, 8) and TT (ranks 8, 8), it
builds the workspace with the public builder, compiles its sweep, runs
`decompose(method="pallas")` for ITERS iterations and the format's reference
method on the same tensor, and checks:

  * every fit is finite;
  * the last fit is within FIT_TOL of the reference method's;
  * mode 0's kernel output matches `kernels/ref.py`'s oracle evaluated in
    float64 on the host, to ORACLE_TOL (largest error over largest entry);
  * the compiled sweep holds a `tpu_custom_call`: the kernels were
    compiled, not interpreted.

--chips 4.  Runs only `method="pallas_sharded"` for the three formats over
four chips, and the single-chip runs they are compared with: every fit
within FIT_TOL of the single-chip fit, and every shard stack spread over
four devices.

The seconds printed are smoke timings of one run, not benchmark results.
The last line of standard output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`;
any failed check raises before it, and the exit code is not 0.  Off a TPU
the script stops before any work.  Everything runs in this one process: a
second process could not reach the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import decompose  # noqa: E402
from repro.core.coo import frostt_like  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.ops import (  # noqa: E402
    make_planned_cp_als,
    make_sharded_planned_cp_als,
    make_sharded_planned_tt,
    make_sharded_planned_tucker,
    plan_cache_clear,
)
from repro.platform import enable_compile_cache  # noqa: E402
from repro.tt import make_planned_tt  # noqa: E402
from repro.tucker import make_planned_tucker  # noqa: E402

PRESET = "nell2_like"
SEED = 0
ITERS = 5
# Fits of two paths that compute the same iterations in f32 in different
# orders; the same bound examples/quickstart.py holds the sharded path to.
FIT_TOL = 1e-4
# The kernels sum each output row in f32 blocks of `blk` nonzeros at full
# matmul precision; against a float64 sum that leaves errors near 1e-6 of
# the largest entry.
ORACLE_TOL = 1e-4
# What a compiled (not interpreted) Pallas kernel is in the HLO text.
KERNEL_MARK = "tpu_custom_call"

# format -> (rank, reference method, single-chip builder, sharded builder)
FORMATS = {
    "cp": (16, "approach1", make_planned_cp_als, make_sharded_planned_cp_als),
    "tucker": ((8, 8, 8), "reference", make_planned_tucker, make_sharded_planned_tucker),
    "tt": ((8, 8), "reference", make_planned_tt, make_sharded_planned_tt),
}


def _require_tpu(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); nothing run")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX found {len(devices)} devices")
    return devices


def _hbm(device) -> str:
    stats = device.memory_stats() or {}
    gib = lambda k: f"{stats[k] / 2**30:.2f} GiB" if k in stats else "n/a"
    return f"in use {gib('bytes_in_use')}, peak {gib('peak_bytes_in_use')}, limit {gib('bytes_limit')}"


def _sweep_args(fmt: str, st) -> tuple[tuple, dict]:
    """The operands `PlannedWorkspace.sweep` takes after the factors."""
    norm_x_sq = jnp.float32(np.sum(st.values.astype(np.float64) ** 2))
    if fmt == "tucker":
        return (norm_x_sq,), {}
    idx, val = jnp.asarray(st.indices), jnp.asarray(st.values)
    return (idx, val, norm_x_sq), ({"first": False} if fmt == "cp" else {})


def _true_factors(fmt: str, state) -> list:
    """The mode factors the kernels take: TT's are the cores' interface
    matrices W_k = transpose(G_k, (1, 0, 2)) reshaped to (I_k, rl * rr)."""
    if fmt != "tt":
        return list(state.factors)
    return [jnp.transpose(c, (1, 0, 2)).reshape(c.shape[1], -1) for c in state.cores]


def _oracle_f64(fmt: str, st, state, mode: int) -> np.ndarray:
    """kernels/ref.py's oracle for one mode, in float64 on the host CPU."""
    fn = {"cp": ref.mttkrp_ref, "tucker": ref.ttmc_ref, "tt": ref.ttcore_ref}[fmt]
    mats = state.cores if fmt == "tt" else state.factors
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        out = fn(
            jnp.asarray(st.indices),
            jnp.asarray(st.values, jnp.float64),
            [jnp.asarray(np.asarray(m), jnp.float64) for m in mats],
            mode,
            st.shape[mode],
        )
        return np.asarray(out)


def _check_fits(label: str, fits, want, tol: float) -> None:
    fits = np.asarray(fits, np.float64)
    if fits.shape != (ITERS,) or not np.isfinite(fits).all():
        raise AssertionError(f"{label}: fits {fits.tolist()} are not {ITERS} finite values")
    gap = np.abs(fits - np.asarray(want, np.float64)).max()
    if gap > tol:
        raise AssertionError(f"{label}: fits differ by {gap:.3e} > {tol:g}")
    print(f"  {label}: |fit gap| {gap:.3e} <= {tol:g}")


def run_one_chip(st) -> None:
    device = jax.devices()[0]
    for fmt, (rank, ref_method, build, _) in FORMATS.items():
        print(f"[{fmt}] rank {rank}")
        t0 = time.perf_counter()
        ws = build(st, rank)
        jax.block_until_ready(jax.tree.leaves([op.layout for op in ws.ops.values()]))
        t_plan = time.perf_counter() - t0
        print(f"  smoke timing: plan build {t_plan:.3f} s "
              f"({ws.plan_bytes() / 2**30:.2f} GiB of layouts)")

        args, kw = _sweep_args(fmt, st)
        facs0 = ws.pad_factors(
            [jnp.ones((s, r), jnp.float32) for s, r in zip(st.shape, ws.lane_ranks)]
        )
        # The drivers trace every matmul at full f32 precision; so does this.
        with jax.default_matmul_precision("highest"):
            t0 = time.perf_counter()
            compiled = ws.lower_sweep(facs0, *args, **kw).compile()
            t_compile = time.perf_counter() - t0
        if KERNEL_MARK not in compiled.as_text():
            raise AssertionError(f"{fmt}: the compiled sweep holds no {KERNEL_MARK}")
        print(f"  smoke timing: sweep compile {t_compile:.3f} s; "
              f"compiled sweep holds {KERNEL_MARK}")

        t0 = time.perf_counter()
        state = decompose(st, rank, format=fmt, method="pallas", planned=ws,
                          iters=ITERS, seed=SEED, verbose=True)
        t_run = time.perf_counter() - t0
        print(f"  smoke timing: decompose {ITERS} iterations {t_run:.3f} s "
              f"(first iteration compiles)")

        facs = ws.pad_factors(_true_factors(fmt, state))
        times = []
        with jax.default_matmul_precision("highest"):
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(ws.sweep(facs, *args, **kw))
                times.append(time.perf_counter() - t0)
        print(f"  smoke timing: steady sweep {statistics.median(times):.4f} s "
              f"(median of {len(times)}: {', '.join(f'{t:.4f}' for t in times)})")

        out = np.asarray(ws.ops[0].output(_true_factors(fmt, state), st.shape[0]))
        want = _oracle_f64(fmt, st, state, 0)
        err = float(np.abs(out - want).max() / np.abs(want).max())
        if not err <= ORACLE_TOL:
            raise AssertionError(f"{fmt}: mode-0 kernel output off the float64 oracle by {err:.3e}")
        print(f"  mode-0 kernel vs float64 oracle: max error / max entry {err:.3e} <= {ORACLE_TOL:g}")
        del ws, facs, facs0, compiled
        plan_cache_clear()
        gc.collect()

        t0 = time.perf_counter()
        ref_state = decompose(st, rank, format=fmt, method=ref_method,
                              iters=ITERS, seed=SEED)
        print(f"  smoke timing: {ref_method} {ITERS} iterations "
              f"{time.perf_counter() - t0:.3f} s")
        print(f"  fits pallas    {[f'{f:.6f}' for f in state.fit_history]}")
        print(f"  fits {ref_method:9s} {[f'{f:.6f}' for f in ref_state.fit_history]}")
        _check_fits(f"{fmt} pallas vs {ref_method}", state.fit_history,
                    ref_state.fit_history, FIT_TOL)
        del state, ref_state
        gc.collect()
        print(f"  HBM: {_hbm(device)}")


def run_sharded(st, chips: int) -> None:
    for fmt, (rank, _, _, build_sharded) in FORMATS.items():
        print(f"[{fmt}] rank {rank}, {chips} chips")
        t0 = time.perf_counter()
        single = decompose(st, rank, format=fmt, method="pallas", iters=ITERS,
                           seed=SEED, verbose=True)
        print(f"  smoke timing: single chip, build + {ITERS} iterations "
              f"{time.perf_counter() - t0:.3f} s")
        plan_cache_clear()
        gc.collect()

        t0 = time.perf_counter()
        ws = build_sharded(st, rank, devices=chips)
        held = {d for s in ws.stacks.values() for d in s.vals.devices()}
        if len(held) != chips:
            raise AssertionError(f"{fmt}: shard stacks sit on {len(held)} devices, not {chips}")
        print(f"  shard stacks on {len(held)} devices: {sorted(d.id for d in held)}")
        sharded = decompose(st, rank, format=fmt, method="pallas_sharded",
                            planned=ws, iters=ITERS, seed=SEED, verbose=True)
        print(f"  smoke timing: sharded, build + {ITERS} iterations "
              f"{time.perf_counter() - t0:.3f} s")
        print(f"  fits single  {[f'{f:.6f}' for f in single.fit_history]}")
        print(f"  fits sharded {[f'{f:.6f}' for f in sharded.fit_history]}")
        _check_fits(f"{fmt} sharded vs single chip", sharded.fit_history,
                    single.fit_history, FIT_TOL)
        del ws, single, sharded
        plan_cache_clear()
        gc.collect()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path, over four chips")
    a = ap.parse_args(argv)
    devices = _require_tpu(a.chips)
    print(f"device: {devices[0].device_kind}, {len(devices)} device(s); "
          f"HBM {_hbm(devices[0])}")
    print(f"compile cache: {enable_compile_cache(ROOT)}")
    st = frostt_like(PRESET, seed=SEED)
    print(f"tensor: {PRESET} shape {st.shape}, {st.nnz:,} nonzeros (FROSTT nell-2 "
          f"has ~77 M; cut for the layout padding), seed {SEED}; "
          f"density {st.nnz / math.prod(st.shape):.2e}")
    if a.chips == 1:
        run_one_chip(st)
    else:
        run_sharded(st, a.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
