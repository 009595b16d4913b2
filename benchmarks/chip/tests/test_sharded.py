"""The sharded path through the harness, on four host devices: a traffic
that names `"method": "pallas_sharded"` runs correct, its padding counts
every shard's slots, and its roofline is per chip.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

sharded_cell.py runs the cell in a process of its own, as JAX fixes its
device count when it first loads.
"""
import json
import os
import subprocess
import sys

import pytest

import sharded_cell
import tiny

ROOT = tiny.CHIP.parent.parent


@pytest.fixture(scope="module")
def out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={sharded_cell.CHIPS}",
               PYTHONPATH=str(ROOT / "src"))
    done = subprocess.run([sys.executable, str(tiny.HERE / "sharded_cell.py")], env=env,
                          capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sharded_cell_is_correct(out):
    assert out["nshards"] == sharded_cell.CHIPS
    for res in (out["plain"], out["trace"]):
        assert res["correct"], res["checks"]
        assert res["attempted"] >= 1 and res["failed"] == 0
        assert res["device"]["count"] == sharded_cell.CHIPS


def test_padding_counts_every_shard(out):
    """D x NB x blk slots per mode, NB the widest shard's block count."""
    slots, nnz = sum(out["slots"]), sum(out["nnz"])
    assert nnz == len(out["slots"]) * 1500
    want = 100.0 * (slots - nnz) / slots
    assert out["trace"]["metrics"]["layout_padding_pct"]["value"] == want


def test_roofline_and_busy_are_per_chip(out):
    red, res = out["reduction"], out["trace"]
    chips = sharded_cell.CHIPS
    assert red["devices"] == chips
    assert red["kernel_s"] == pytest.approx(sum(sharded_cell.KERNEL_US) / chips * 1e-6)
    assert red["collective_s"] == pytest.approx(sharded_cell.COLLECTIVE_US * 1e-6)
    assert red["busy_s"] == pytest.approx(red["kernel_s"] + red["collective_s"])
    assert res["device"]["busy_s"] == red["busy_s"]
    metrics = res["metrics"]
    iterations = round(1e3 * red["kernel_s"] / metrics["kernel_ms"]["value"])
    one_chip = 100.0 * out["bound_one_chip_s"] * iterations / red["kernel_s"]
    got = metrics["kernel_roofline_pct"]["value"]
    assert got == 100.0 * (out["bound_one_chip_s"] / chips) * iterations / red["kernel_s"]
    assert got == pytest.approx(one_chip / chips, rel=1e-12)
