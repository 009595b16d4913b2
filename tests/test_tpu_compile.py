"""Compile the planned kernels for a described TPU v5e, from shapes only.

Nothing runs: the TPU compiler is installed here and compiles for a chip
that is described, not attached, so these tests catch what interpret mode
cannot — block shapes the tiling refuses, gathers or reshapes Mosaic cannot
lower, SMEM and VMEM overflows.  The topology is described inside a module
fixture, never at import (one process at a time may load the TPU library);
every test skips where it cannot be described.
"""
import base64
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


from repro.core.pms import DEFAULT_BLK_CHOICES, DEFAULT_TILE_CHOICES, search
from repro.core.coo import frostt_like
from repro.kernels import blocked
from repro.kernels.blocked import chunk_blocks
from repro.kernels.mttkrp_pallas import mttkrp_pallas_call, rank_padded
from repro.kernels.tt_pallas import ttcore_pallas_call
from repro.kernels.ttm_pallas import ttmc_pallas_call
from repro.platform import enable_compile_cache

# Blocks per mode of the nell2_like preset at the default configuration.
NELL2_BLOCKS = 169_799


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _args(sharding, nblocks, blk, tile_i, in_tiles, widths, rows=4096):
    """ShapeDtypeStructs of one plan's device arrays plus its padded factors."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    n_in = len(in_tiles)
    stream = lambda dt: s((nblocks, 1, blk), dt)
    return (
        s((nblocks,), jnp.int32),
        tuple(s((nblocks,), jnp.int32) for _ in range(n_in)),
        stream(jnp.float32),
        stream(jnp.int32),
        tuple(stream(jnp.int32) for _ in range(n_in)),
        tuple(s((max(rows, t), w), jnp.float32) for t, w in zip(in_tiles, widths)),
    )


def _compile(fn, args, **static):
    compiled = jax.jit(lambda *a: fn(*a, interpret=False, **static)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("n_in,nblocks", [(2, NELL2_BLOCKS), (4, NELL2_BLOCKS), (2, 512)])
def test_mttkrp_compiles(one_chip, n_in, nblocks):
    """3- and 5-mode MTTKRP; at the nell2_like block count the grid is split
    into SMEM-sized chunks."""
    in_tiles = (256,) * n_in
    args = _args(one_chip, nblocks, 256, 256, in_tiles, (128,) * n_in)
    text = _compile(mttkrp_pallas_call, args, tile_i=256, in_tiles=in_tiles,
                    out_rows=4096)
    chunks = -(-nblocks // chunk_blocks(1 + n_in))
    assert text.count("custom_call_target=\"tpu_custom_call\"") == chunks


@pytest.mark.parametrize("n_in", [2, 3])
def test_ttmc_compiles(one_chip, n_in):
    """TTMc on 3 and 4 modes with core ranks 8 (P = 64 and 512 columns)."""
    in_tiles, ranks = (256,) * n_in, (8,) * n_in
    args = _args(one_chip, 2048, 256, 256, in_tiles, (rank_padded(8),) * n_in)
    _compile(ttmc_pallas_call, args, tile_i=256, in_tiles=in_tiles,
             in_ranks=ranks, out_rows=4096)


@pytest.mark.parametrize("mode", [0, 1, 2, 3])
def test_tt_compiles(one_chip, mode):
    """TT on 4 modes, bonds (8, 8, 8): modes 1 and 2 have interior bonds on
    both sides."""
    bonds = (1, 8, 8, 8, 1)
    pairs = tuple((bonds[k], bonds[k + 1]) for k in range(4))
    in_pairs = tuple(p for k, p in enumerate(pairs) if k != mode)
    in_tiles = (256,) * 3
    args = _args(one_chip, 2048, 256, 256, in_tiles,
                 tuple(rank_padded(a * b) for a, b in in_pairs))
    _compile(ttcore_pallas_call, args, tile_i=256, in_tiles=in_tiles,
             in_rank_pairs=in_pairs, n_left=mode, out_rows=4096)


def test_mttkrp_compiles_at_largest_admitted_config(one_chip):
    """The largest tiles and block the PMS admits at rank 16 fit the VMEM
    limit the kernel compiles with."""
    st = frostt_like("tiny")
    admitted = search(st, 0, 16, top_k=10_000)
    biggest = max(admitted, key=lambda e: e.vmem_bytes)
    c, blk = biggest.cfg.cache, biggest.cfg.dma.blk
    assert blk == max(DEFAULT_BLK_CHOICES)
    assert c.tile_i == max(DEFAULT_TILE_CHOICES)
    in_tiles = c.input_tiles(2)
    args = _args(one_chip, 512, blk, c.tile_i, in_tiles, (128, 128))
    _compile(mttkrp_pallas_call, args, tile_i=c.tile_i, in_tiles=in_tiles,
             out_rows=4096)


def _kernel_body(fn, args, **static) -> bytes:
    """The serialized Mosaic body of the first kernel in the lowered program."""
    text = jax.jit(lambda *a: fn(*a, interpret=False, **static)).lower(*args).as_text()
    body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)', text).group(1)
    return base64.b64decode(body + "=" * (-len(body) % 4))


def test_kernel_body_carries_no_checkout_path(one_chip, monkeypatch):
    """The kernel body, part of the persistent cache key, holds source
    locations; after `enable_compile_cache` they are relative to the
    checkout, so a checkout at another path hits the same cache entries."""
    root = Path(blocked.__file__).resolve().parents[3]
    args = _args(one_chip, 512, 256, 256, (256, 256), (128, 128))
    static = dict(tile_i=256, in_tiles=(256, 256), out_rows=4096)
    assert str(root).encode() in _kernel_body(mttkrp_pallas_call, args, **static)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_hlo_source_file_canonicalization_regex)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        enable_compile_cache(root)
        body = _kernel_body(mttkrp_pallas_call, args, **static)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_hlo_source_file_canonicalization_regex", prev[1])
    assert b"src/repro/kernels/blocked.py" in body
    assert str(root).encode() not in body


def _mosaic_text(body: bytes) -> str:
    """A serialized Mosaic kernel body decoded back to MLIR text."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir
    from jaxlib.mlir.passmanager import PassManager

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    with ctx, ir.Location.unknown():
        ctx.allow_unregistered_dialects = True  # the serialized, versioned dialect
        module = ir.Module.parse(body)
        PassManager.parse("builtin.module(mosaic-serde{serialize=false})").run(module.operation)
        return str(module)


# The benchmark's kernels at its tiles (256) and block (256): nell-2 CP at
# rank 16, Tucker at ranks (8, 8, 8), TT at ranks (8, 8) for its middle mode.
_BENCH_KERNELS = {
    "mttkrp": (mttkrp_pallas_call, dict(out_rows=4096)),
    "ttmc": (ttmc_pallas_call, dict(in_ranks=(8, 8), out_rows=4096)),
    "ttcore": (ttcore_pallas_call, dict(in_rank_pairs=((1, 8), (8, 1)), n_left=1,
                                        out_rows=4096)),
}


@pytest.mark.parametrize("kind", sorted(_BENCH_KERNELS))
def test_kernel_matmuls_are_single_pass_bf16(one_chip, kind):
    """Every matmul in the kernel body is one bf16 MXU pass: no operand is
    f32 and none asks for the six-pass float32 contraction, also when traced
    under the drivers' float32 default matmul precision (`f32_matmuls`)."""
    fn, static = _BENCH_KERNELS[kind]
    args = _args(one_chip, 512, 256, 256, (256, 256), (128, 128))
    with jax.default_matmul_precision("highest"):
        body = _kernel_body(fn, args, tile_i=256, in_tiles=(256, 256), **static)
    text = _mosaic_text(body)
    matmuls = [line for line in text.splitlines() if "tpu.matmul" in line]
    assert matmuls
    assert "contract_precision" not in text
    for line in matmuls:
        lhs, rhs = re.search(r":\s*vector<([^>]*)>,\s*vector<([^>]*)>", line).groups()
        assert lhs.endswith("xbf16") and rhs.endswith("xbf16"), line


@pytest.mark.parametrize("fmt", ["cp", "tucker", "tt"])
def test_sweep_compiles_under_driver_precision(one_chip, fmt, monkeypatch, small_tensor):
    """Each format's whole sweep, its operands given as shapes on the
    described chip, compiles for the v5e under the float32 default matmul
    precision the drivers trace with (`f32_matmuls`): the kernels' bf16
    matmuls keep their own single-pass precision inside it."""
    from repro.kernels.ops import make_planned_cp_als
    from repro.tt.als import make_planned_tt
    from repro.tucker.hooi import make_planned_tucker

    norm = jnp.float32(1.0)
    stream = (jnp.asarray(small_tensor.indices), jnp.asarray(small_tensor.values), norm)
    ws, args = {
        "cp": lambda: (make_planned_cp_als(small_tensor, 16), stream),
        "tucker": lambda: (make_planned_tucker(small_tensor, (8, 8, 8)), (norm,)),
        "tt": lambda: (make_planned_tt(small_tensor, (8, 8)), stream),
    }[fmt]()
    facs = tuple(jnp.zeros(s, jnp.float32) for s in zip(ws.padded_rows, ws.rank_pads))
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                          ws._sweep_operands(facs, args))
    monkeypatch.setattr(blocked, "interpret_mode", lambda: False)
    with jax.default_matmul_precision("highest"):
        for kwargs in ws._sweep_variants():
            text = ws._jitted_sweep().lower(*shapes, **kwargs).compile().as_text()
            assert "tpu_custom_call" in text
