"""Calibration CLI: fit this machine's TPUSpec and warm the autotune cache.

  PYTHONPATH=src python scripts/calibrate.py [options]

Runs the measured-roofline calibration workflow (repro.tune.calibrate):
microbenchmarks (streaming-copy bandwidth + segment-matmul FLOP/s), a
block-sweep least-squares fit of (hbm_bw, peak_flops_f32), the
`obs.calibrate` validation join, and a persisted fitted spec in the autotune
cache — after which `pms.search(spec="measured")` and
`decompose(spec="measured")` price configurations with numbers this backend
actually achieves (docs/autotune.md).

Options:
  --preset NAME     frostt_like preset for the sweep samples (default: tiny)
  --rank R          CP rank of the calibration sweeps (default: 8)
  --reps N          timed repetitions per sample (default: 2)
  --cache-dir PATH  override $REPRO_AUTOTUNE_DIR for this run
  --dry-run         fit + report, but do not write the cache
  --check-hit       after fitting, assert a warm `spec="measured"` resolve
                    serves the stored spec without re-calibrating (the CI
                    calibration smoke) — exits non-zero on a miss
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="tiny")
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--cache-dir", default=None)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--check-hit", action="store_true")
    a = ap.parse_args(argv)
    if a.cache_dir:
        os.environ["REPRO_AUTOTUNE_DIR"] = a.cache_dir

    from repro.platform import enable_compile_cache
    from repro.tune import (
        calibrate,
        calibrate_and_store,
        cache_path,
        current_backend,
        default_cache,
        resolve_spec,
    )

    enable_compile_cache(ROOT)
    kwargs = dict(preset=a.preset, rank=a.rank, reps=a.reps)
    if a.dry_run:
        result = calibrate(**kwargs)
    else:
        result = calibrate_and_store(**kwargs)

    spec = result.spec
    print(f"backend: {result.backend}")
    if result.stream_hbm_bw is not None:
        print(f"microbench: stream bw {result.stream_hbm_bw/1e9:.2f} GB/s, "
              f"matmul {result.matmul_peak_flops_f32/1e9:.1f} GFLOP/s (f32)")
    print(f"fitted: hbm_bw {spec.hbm_bw/1e9:.3f} GB/s, "
          f"peak_flops_f32 {spec.peak_flops_f32/1e9:.1f} GFLOP/s "
          f"(sum-model residual {result.residual_rel:.1%})")
    print(f"validation (obs.calibrate achieved_pct, default -> measured):")
    for row in result.validation:
        print(f"  {row['label']:32s} {row['achieved_pct_default']:10.4f}% -> "
              f"{row['achieved_pct_measured']:7.2f}%")
    if a.dry_run:
        print("dry run: cache not written")
        return 0
    print(f"stored -> {cache_path()} (backend {result.backend!r})")

    if a.check_hit:
        # The warm-path assertion CI gates on: the spec must come back from
        # the cache, not from a fresh calibration.
        got = default_cache().get_spec(current_backend())
        if got != spec:
            print("check-hit FAILED: cached spec does not match the fit",
                  file=sys.stderr)
            return 1
        assert resolve_spec("measured", calibrate_on_miss=False) == spec
        print("check-hit OK: warm spec='measured' resolves from the cache")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
