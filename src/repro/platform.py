"""Choices the code makes from the platform it runs on.

  * `interpret_mode()` — Pallas kernels run compiled on a TPU and in
    interpret mode on every other backend (the CPU test machines).  It is
    not a user option: the platform decides.
  * `device_spec()` — the hardware constants of the chip, looked up by
    `device_kind` in `core.memctrl.TPU_SPECS`.  A TPU kind the table does
    not hold is an error, never another chip's numbers.
  * `enable_compile_cache(root)` — JAX's persistent compilation cache, for
    entry-point scripts only (never called at import).
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

from .core.memctrl import TPU_SPECS, TPUSpec

__all__ = ["interpret_mode", "device_spec", "enable_compile_cache", "CACHE_DIRNAME"]

CACHE_DIRNAME = ".jax_cache"


def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


def device_spec(device_kind: str | None = None) -> TPUSpec:
    """The `TPUSpec` of `device_kind` (default: the first device's kind).

    Off a TPU, with no kind given, this is `TPUSpec()` — the v5e, the chip
    the PMS models when the code is developed on a CPU.  A TPU kind that is
    not in `TPU_SPECS` raises ValueError."""
    if device_kind is None:
        if jax.default_backend() != "tpu":
            return TPUSpec()
        device_kind = jax.devices()[0].device_kind
    try:
        return TPU_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware constants for device kind {device_kind!r}: add its "
            f"published peaks to repro.core.memctrl.TPU_SPECS (known: "
            f"{sorted(TPU_SPECS)})"
        ) from None


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, the cache stays in that
    directory and no other is set.  Otherwise the cache goes to the fixed
    directory `<root>/.jax_cache` — a path that does not move between runs,
    so a second run in the same checkout finds what the first compiled.

    A compiled Pallas kernel carries its source locations inside its
    serialized body, which is part of the cache key, so source paths are
    made relative to `root`: a checkout at another path then finds the same
    entries.  Call it from an entry point before the first compile."""
    root = Path(root).resolve()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex", "^" + re.escape(f"{root}{os.sep}")
    )
    return path
