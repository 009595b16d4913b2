"""Programmable memory-controller configuration (paper Sec. 5).

The paper's controller has three engines whose parameters are fixed at FPGA
synthesis time and share a finite on-chip SRAM budget (BRAM/URAM).  The TPU
analogue fixes the parameters at *trace/compile* time and shares the VMEM
budget.  The mapping of each paper parameter (Sec. 5.2):

  Cache Engine  — cache-line width        -> factor-tile row width  (R_pad lanes)
                  number of cache lines   -> tile rows (tile_j / tile_k)
                  associativity           -> resident tiles per operand (1 in the
                                             kernel; modeled for the PMS)
  DMA Engine    — number of DMAs          -> concurrent BlockSpec streams (fixed
                                             by kernel arity)
                  buffers per DMA         -> double-buffer depth (pipelined grid)
                  DMA buffer size         -> blk (non-zeros per grid step)
  Remapper      — DMA buffer size         -> remap chunk
                  tensor-element width    -> index+value bytes
                  max address pointers    -> pointer_budget (hierarchical remap
                                             when a mode exceeds it)
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "CacheEngineConfig",
    "DMAEngineConfig",
    "RemapperConfig",
    "MemoryControllerConfig",
    "TPUSpec",
    "TPU_SPECS",
    "spec_to_dict",
    "spec_from_dict",
    "config_to_dict",
    "config_from_dict",
]


@dataclasses.dataclass(frozen=True)
class CacheEngineConfig:
    tile_i: int = 256  # output-tile rows resident in VMEM (accumulator)
    tile_j: int = 256  # input factor tile rows ("number of cache lines")
    tile_k: int = 256
    resident_tiles: int = 1  # "associativity": tiles kept per operand

    def input_tiles(self, n_in: int = 2) -> tuple[int, ...]:
        """Per-input-mode tile sizes for an N-mode tensor (n_in = N-1 input
        factor tiles resident in VMEM): the first input mode uses tile_j,
        every further one tile_k."""
        assert n_in >= 1
        return ((self.tile_j,) + (self.tile_k,) * (n_in - 1))[:n_in]


@dataclasses.dataclass(frozen=True)
class DMAEngineConfig:
    blk: int = 256  # non-zeros per grid step ("DMA buffer size")
    buffers: int = 2  # double buffering depth (Pallas pipelines grid steps)


@dataclasses.dataclass(frozen=True)
class RemapperConfig:
    pointer_budget: int = 1 << 20  # max address pointers on-chip (Sec. 3.1)
    index_bytes: int = 4
    value_bytes: int = 4


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    """Hardware constants of one chip.  The defaults are the TPU v5e.

    Published (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
    16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
    (4 links of 50 GB/s).  Not published: `peak_flops_f32` is an assumption
    (half the bf16 peak); the VMEM and SMEM capacities are the ones the
    compiler reports for "TPU v5 lite"
    (`jax.experimental.pallas.tpu.get_tpu_info()`)."""

    peak_flops: float = 197e12  # bf16
    peak_flops_f32: float = 98.5e12
    hbm_bw: float = 819e9  # bytes/s
    vmem_bytes: int = 128 * 1024 * 1024
    vmem_usable_frac: float = 0.5  # compiler scratch, double buffers
    ici_bw_per_link: float = 50e9  # bytes/s/link
    ici_links: int = 4  # 2D torus on v5e: 4 links/chip
    hbm_bytes: int = 16 * 1024**3
    smem_bytes: int = 1024 * 1024


# Hardware constants by `jax.Device.device_kind` (see `repro.platform`).
TPU_SPECS: dict[str, TPUSpec] = {
    "TPU v5 lite": TPUSpec(),
}


@dataclasses.dataclass(frozen=True)
class MemoryControllerConfig:
    cache: CacheEngineConfig = CacheEngineConfig()
    dma: DMAEngineConfig = DMAEngineConfig()
    remapper: RemapperConfig = RemapperConfig()

    def _vmem(self, out_cols: int, in_widths: tuple[int, ...], scratch_cols: int) -> int:
        """VMEM of one kernel instance (kernels/blocked.py), shared by the
        three kernel models.

        Double-buffered (x dma.buffers): the accumulator tile twice (carried
        in and written out, `out_cols` lanes), one resident factor tile per
        input mode at its own lane width, and the stream blocks — one
        (1, blk) row per stream, which VMEM pads to 8 sublanes.  Single
        copies: the one-hot gather (widest input tile x blk), the one-hot
        segment matrix (tile_i x blk), and `scratch_cols` lanes of (blk, .)
        per-element intermediates.  Element widths come from the Remapper
        configuration."""
        c, d, r = self.cache, self.dma, self.remapper
        in_tiles = c.input_tiles(len(in_widths))
        tiles = (
            2 * c.tile_i * out_cols
            + sum(t * w for t, w in zip(in_tiles, in_widths)) * c.resident_tiles
        ) * r.value_bytes
        stream = 8 * d.blk * (r.value_bytes + (len(in_widths) + 1) * r.index_bytes)
        onehots = d.blk * (max(in_tiles) + c.tile_i) * r.value_bytes
        return d.buffers * (tiles + stream) + onehots + d.blk * scratch_cols * r.value_bytes

    def vmem_bytes(self, rank_padded: int, n_in: int = 2) -> int:
        """VMEM footprint of one MTTKRP kernel instance: R_pad-wide tiles and
        the gathered rows plus their running Hadamard product as scratch."""
        return self._vmem(rank_padded, (rank_padded,) * n_in, 2 * rank_padded)

    def fits(self, spec: TPUSpec, rank_padded: int, n_in: int = 2) -> bool:
        return self.vmem_bytes(rank_padded, n_in) <= spec.vmem_bytes * spec.vmem_usable_frac

    def vmem_bytes_ttmc(self, out_cols_padded: int, in_rank_pads: tuple[int, ...]) -> int:
        """VMEM footprint of one TTM-chain kernel instance.

        Differs from the MTTKRP model in the tile widths: the output
        accumulator is a *core-tensor slice* of out_cols_padded =
        cols_padded(prod input ranks) lanes — the Kronecker chain widens the
        accumulator multiplicatively in the ranks, which is exactly why the
        TTMc search needs its own fit constraint — and each resident input
        factor tile carries its own lane padding rank_padded(R_m) instead of
        a shared R_pad.  Scratch: the widest gathered rows plus the spread
        and the running Kronecker product at the output width."""
        return self._vmem(
            out_cols_padded, tuple(in_rank_pads),
            max(in_rank_pads) + 2 * out_cols_padded,
        )

    def fits_ttmc(self, spec: TPUSpec, out_cols_padded: int, in_rank_pads: tuple[int, ...]) -> bool:
        return (
            self.vmem_bytes_ttmc(out_cols_padded, in_rank_pads)
            <= spec.vmem_bytes * spec.vmem_usable_frac
        )

    def vmem_bytes_tt(
        self,
        out_cols_padded: int,
        in_rank_pads: tuple[int, ...],
        iface_cols: int,
    ) -> int:
        """VMEM footprint of one TT-core kernel instance.

        Same tile/stream structure as the TTMc model — the output accumulator
        carries out_cols_padded = rank_padded(rl_m*rr_m) lanes and each
        resident core-interface tile its own rank_padded(rl_k*rr_k) — plus
        the two-interface scratch: the left and right chain vectors live at
        (blk, iface_cols) where iface_cols bounds the widest left- and
        right-chain intermediates, next to the widest gathered rows and
        their spread product."""
        return self._vmem(
            out_cols_padded, tuple(in_rank_pads),
            iface_cols + 2 * max(max(in_rank_pads), out_cols_padded),
        )

    def fits_tt(
        self,
        spec: TPUSpec,
        out_cols_padded: int,
        in_rank_pads: tuple[int, ...],
        iface_cols: int,
    ) -> bool:
        return (
            self.vmem_bytes_tt(out_cols_padded, in_rank_pads, iface_cols)
            <= spec.vmem_bytes * spec.vmem_usable_frac
        )


# ---------------------------------------------------------------------------
# JSON-ready (de)serialization — the autotune cache (repro.tune.cache) persists
# fitted TPUSpecs and winning MemoryControllerConfigs across processes.  The
# converters live here, next to the dataclasses whose schema they mirror.
# ---------------------------------------------------------------------------


def _from_known_fields(cls, d: dict):
    """Rebuild a dataclass from a plain dict, rejecting unknown keys (a key
    this schema does not know about means the entry was written by a different
    code version — the caller treats that as a cache miss, never a crash)."""
    if not isinstance(d, dict):
        raise ValueError(f"{cls.__name__}: expected a dict, got {type(d).__name__}")
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(d) - names
    if unknown:
        raise ValueError(f"{cls.__name__}: unknown fields {sorted(unknown)}")
    return cls(**d)


def spec_to_dict(spec: TPUSpec) -> dict:
    """TPUSpec -> plain JSON-ready dict."""
    return dataclasses.asdict(spec)


def spec_from_dict(d: dict) -> TPUSpec:
    """Plain dict -> TPUSpec.  Raises ValueError on unknown fields."""
    return _from_known_fields(TPUSpec, d)


def config_to_dict(cfg: MemoryControllerConfig) -> dict:
    """MemoryControllerConfig -> nested JSON-ready dict."""
    return dataclasses.asdict(cfg)


def config_from_dict(d: dict) -> MemoryControllerConfig:
    """Nested dict -> MemoryControllerConfig.  Raises ValueError on unknown
    fields at any level (version drift reads as invalid, not as silence)."""
    if not isinstance(d, dict):
        raise ValueError(f"config: expected a dict, got {type(d).__name__}")
    known = {"cache", "dma", "remapper"}
    unknown = set(d) - known
    if unknown:
        raise ValueError(f"config: unknown fields {sorted(unknown)}")
    return MemoryControllerConfig(
        cache=_from_known_fields(CacheEngineConfig, d.get("cache", {})),
        dma=_from_known_fields(DMAEngineConfig, d.get("dma", {})),
        remapper=_from_known_fields(RemapperConfig, d.get("remapper", {})),
    )
