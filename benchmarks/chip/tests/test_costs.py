"""The compulsory-work counts behind kernel_roofline_pct.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Bytes are checked against hand-worked counts for the benchmark's cells, and
the counts must not move with the layout: a plan built at another block
size, or factors padded to other lane widths, is the same compulsory work.
"""
import inspect
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(CHIP.parent.parent / "src"))

from costs import cp, tt, tucker  # noqa: E402


def _config(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def test_cp_bytes_by_hand():
    nell2, uber = _config("frostt-nell2-2m"), _config("frostt-uber")
    work = cp.kernel_work(nell2["shape"], nell2["nnz"], 16)
    assert [w["bytes"] for w in work] == [2_000_000 * 16 + 50_094 * 16 * 4] * 3
    work = cp.kernel_work(uber["shape"], uber["nnz"], 16)
    assert [w["bytes"] for w in work] == [3_309_490 * 20 + 3_064 * 16 * 4] * 4


def test_flops_by_hand():
    shape, nnz = (12_092, 9_184, 28_818), 2_000_000
    # CP: value x row, one Hadamard product, one add: 3 * 16 per nonzero.
    assert [w["flops"] for w in cp.kernel_work(shape, nnz, 16)] == [nnz * 48] * 3
    # Tucker (8, 8, 8): value x row (8), Kronecker product (64), add (64).
    assert [w["flops"] for w in tucker.kernel_work(shape, nnz, (8, 8, 8))] == [nnz * 136] * 3
    # TT (8, 8): the end modes chain one (8, 8) core slice with an 8-vector
    # (64 multiplies, 56 adds), scale (1) and add (8); the middle mode scales
    # one 8-vector (8), forms an 8 x 8 Kronecker product (64) and adds (64).
    assert [w["flops"] for w in tt.kernel_work(shape, nnz, (8, 8))] == [nnz * 129, nnz * 136, nnz * 129]


def test_tucker_and_tt_bytes_by_hand():
    shape, nnz = (12_092, 9_184, 28_818), 2_000_000
    stream = nnz * 16
    want = [stream + 4 * (9_184 * 8 + 28_818 * 8 + 12_092 * 64),
            stream + 4 * (12_092 * 8 + 28_818 * 8 + 9_184 * 64),
            stream + 4 * (12_092 * 8 + 9_184 * 8 + 28_818 * 64)]
    assert [w["bytes"] for w in tucker.kernel_work(shape, nnz, (8, 8, 8))] == want
    # TT cores (1, I0, 8), (8, I1, 8), (8, I2, 1); B_m is (I_m, rl * rr).
    core = [12_092 * 8, 9_184 * 64, 28_818 * 8]
    want = [stream + 4 * sum(core) for _ in range(3)]
    assert [w["bytes"] for w in tt.kernel_work(shape, nnz, (8, 8))] == want


@pytest.mark.parametrize("module", [cp, tucker, tt])
def test_counts_take_no_layout(module):
    """The counts are a function of the tensor's shape, its nonzeros and the
    true ranks alone: no block size, tile or lane width goes in."""
    assert list(inspect.signature(module.kernel_work).parameters) == ["shape", "nnz", "rank"]


@pytest.mark.parametrize("fmt, rank, builder", [
    ("cp", 16, "repro.kernels.ops:make_planned_cp_als"),
    ("tucker", (4, 4, 4), "repro.tucker:make_planned_tucker"),
    ("tt", (4, 4), "repro.tt:make_planned_tt"),
])
def test_counts_ignore_blk_and_lane_padding(fmt, rank, builder):
    """Plans at two block sizes differ in slots and blocks, lane widths
    differ from the true ranks, and the count stays the one the shape,
    nonzeros and true ranks give."""
    import tensors
    from repro.core.coo import SparseTensor
    from repro.core.memctrl import MemoryControllerConfig
    from run import load_callable

    import dataclasses

    config = {"shape": [300, 200, 500], "nnz": 3000, "skew": [1.0] * 3, "structure_seed": 0}
    idx, vals, shape = tensors.generate(config, 0)
    st = SparseTensor(idx, vals, shape)
    costs = {"cp": cp, "tucker": tucker, "tt": tt}[fmt]
    slots, lanes = set(), set()
    for blk in (64, 256):
        base = MemoryControllerConfig()
        cfg = dataclasses.replace(base, dma=dataclasses.replace(base.dma, blk=blk))
        ws = load_callable(builder)(st, rank, cfg=cfg)
        slots.add(sum(op.plan.nblocks * op.plan.blk for op in ws.ops.values()))
        lanes.add(ws.rank_pads)
        assert costs.kernel_work(ws.shape, st.nnz, rank) == costs.kernel_work(shape, 3000, rank)
    assert len(slots) == 2
    assert all(p >= 128 for pads in lanes for p in pads)
