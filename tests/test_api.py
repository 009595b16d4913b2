"""The unified `decompose()` facade (repro/api.py): format dispatch is
bit-for-bit identical to the legacy per-format drivers, rank normalization
broadcasts per format, errors are caught at the facade, and the shared
`PlannedWorkspace.drive` pads each mode exactly ONCE per decomposition."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

import repro.kernels.workspace as workspace_mod
from repro.api import decompose
from repro.core.coo import synthetic_tensor
from repro.core.cp_als import cp_als
from repro.core.memctrl import CacheEngineConfig, DMAEngineConfig, MemoryControllerConfig
from repro.tt import tt_als
from repro.tucker import tucker_hooi

SMALL_CFG = MemoryControllerConfig(
    cache=CacheEngineConfig(tile_i=16, tile_j=16, tile_k=16),
    dma=DMAEngineConfig(blk=32),
)


# ---------------------------------------------------------------------------
# facade == legacy drivers, bit for bit
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(
    nnz=hst.integers(1, 200),
    base=hst.tuples(hst.integers(4, 16), hst.integers(4, 16), hst.integers(4, 16)),
    extra=hst.sampled_from([(), (7,), (7, 6)]),
    rank=hst.integers(1, 4),
    seed=hst.integers(0, 99),
)
def test_decompose_matches_legacy_drivers(nnz, base, extra, rank, seed):
    """Property (stub-compatible): on 3/4/5-mode tensors, the facade's fit
    history equals the legacy `cp_als` / `tucker_hooi` / `tt_als` histories
    BIT FOR BIT — `decompose` holds no algorithm logic, it only normalizes
    the rank and dispatches.  A degenerate tensor (one nonzero) can drive
    both to the same NaN fit, which counts as equal."""
    dims = base + extra
    st_t = synthetic_tensor(dims, nnz, seed=seed, skew=0.5)
    # CP: 'approach1' is the eager compute-pattern baseline (CP's oracle role)
    a = decompose(st_t, rank, format="cp", method="approach1", iters=2, seed=seed)
    b = cp_als(st_t, rank, method="approach1", iters=2, seed=seed)
    np.testing.assert_array_equal(a.fit_history, b.fit_history)
    # Tucker: the pure-jnp reference
    tr = tuple(min(rank, 3) for _ in dims)
    a = decompose(st_t, tr, format="tucker", method="reference", iters=2, seed=seed)
    b = tucker_hooi(st_t, tr, method="reference", iters=2, seed=seed)
    np.testing.assert_array_equal(a.fit_history, b.fit_history)
    # TT: the pure-jnp reference, random init keyed by the same seed
    bond = (min(rank, 3),) * (len(dims) - 1)
    a = decompose(st_t, bond, format="tt", method="reference", iters=2,
                  seed=seed, init="random")
    b = tt_als(st_t, bond, method="reference", iters=2, seed=seed, init="random")
    np.testing.assert_array_equal(a.fit_history, b.fit_history)


def test_decompose_pallas_matches_legacy(tiny_tensor):
    """The planned-pallas path through the facade is the legacy planned path
    (same workspaces, same jitted sweeps), for all three formats."""
    a = decompose(tiny_tensor, 4, format="cp", iters=2, cfg=SMALL_CFG)
    b = cp_als(tiny_tensor, 4, method="pallas", iters=2, cfg=SMALL_CFG)
    assert a.fit_history == b.fit_history
    a = decompose(tiny_tensor, (3, 3, 3), format="tucker", iters=2, cfg=SMALL_CFG)
    b = tucker_hooi(tiny_tensor, (3, 3, 3), method="pallas", iters=2, cfg=SMALL_CFG)
    assert a.fit_history == b.fit_history
    a = decompose(tiny_tensor, (3, 3), format="tt", iters=2, cfg=SMALL_CFG,
                  init="random")
    b = tt_als(tiny_tensor, (3, 3), method="pallas", iters=2, cfg=SMALL_CFG,
               init="random")
    assert a.fit_history == b.fit_history


def test_decompose_rank_broadcast(tiny_tensor):
    """An int rank broadcasts per format: to all N modes for Tucker, to the
    N-1 interior bonds for TT."""
    a = decompose(tiny_tensor, 3, format="tucker", method="reference", iters=1)
    b = decompose(tiny_tensor, (3, 3, 3), format="tucker", method="reference", iters=1)
    assert a.fit_history == b.fit_history
    assert a.core.shape == (3, 3, 3)
    a = decompose(tiny_tensor, 3, format="tt", method="reference", iters=1,
                  init="random")
    assert a.tt_ranks == (3, 3)


def test_decompose_errors(tiny_tensor):
    with pytest.raises(ValueError, match="expected 'cp', 'tucker' or 'tt'"):
        decompose(tiny_tensor, 4, format="cpd")
    with pytest.raises(ValueError, match="single integer rank"):
        decompose(tiny_tensor, (4, 4, 4), format="cp")
    # format-specific validation still lives with the drivers
    with pytest.raises(ValueError, match="3 entries for a 3-mode tensor"):
        decompose(tiny_tensor, (4, 4, 4), format="tt")


# ---------------------------------------------------------------------------
# plan-amortization contract of the shared driver
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "format,rank",
    [("cp", 4), ("tucker", (3, 3, 3)), ("tt", (3, 3))],
)
def test_drive_pads_each_mode_exactly_once(tiny_tensor, monkeypatch, format, rank):
    """`PlannedWorkspace.drive` pads the factors ONCE for the whole
    decomposition — exactly one `pad_factor` call per mode through the
    shared driver, not nmodes x iters (the sweeps stay in padded space)."""
    calls = []
    real = workspace_mod.pad_factor

    def counting(f, rows, rp):
        calls.append((rows, rp))
        return real(f, rows, rp)

    monkeypatch.setattr(workspace_mod, "pad_factor", counting)
    kwargs = {"init": "random"} if format == "tt" else {}
    state = decompose(
        tiny_tensor, rank, format=format, iters=3, cfg=SMALL_CFG, **kwargs
    )
    assert len(state.fit_history) == 3
    assert len(calls) == tiny_tensor.nmodes
