"""The plain references, on the CPU at a tiny size, against contractions
worked out by hand on the dense tensor.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from reference import cp, numerics, tt, tucker  # noqa: E402

SHAPE = (5, 4, 6)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    lin = rng.choice(np.prod(SHAPE), size=40, replace=False)
    idx = np.stack(np.unravel_index(lin, SHAPE), axis=1).astype(np.int32)
    vals = rng.standard_normal(40)
    dense = np.zeros(SHAPE)
    dense[tuple(idx.T)] = vals
    return idx, vals, dense, rng


def _x(ar, idx, vals):
    return {"idx": ar.put_index(idx), "vals": ar.put(vals),
            "norm_x_sq": ar.put(np.sum(vals ** 2))}


def test_mttkrp_by_hand(data):
    idx, vals, dense, rng = data
    f = [rng.standard_normal((s, 3)) for s in SHAPE]
    ar = numerics.exact()
    with ar.scope():
        got = [np.asarray(cp.mttkrp(ar, ar.put_index(idx), ar.put(vals), [ar.put(a) for a in f], m, SHAPE[m]))
               for m in range(3)]
    want = [np.einsum("ijk,jr,kr->ir", dense, f[1], f[2]),
            np.einsum("ijk,ir,kr->jr", dense, f[0], f[2]),
            np.einsum("ijk,ir,jr->kr", dense, f[0], f[1])]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-12, atol=1e-12)


def test_ttmc_by_hand(data):
    idx, vals, dense, rng = data
    f = [rng.standard_normal((s, r)) for s, r in zip(SHAPE, (2, 3, 2))]
    ar = numerics.exact()
    with ar.scope():
        got = np.asarray(tucker.ttmc(ar, ar.put_index(idx), ar.put(vals), [ar.put(a) for a in f], 1, SHAPE[1]))
    # Columns row-major over the ascending input modes (0 slow, 2 fast).
    want = np.einsum("ijk,ia,kc->jac", dense, f[0], f[2]).reshape(SHAPE[1], -1)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_ttcore_by_hand(data):
    idx, vals, dense, rng = data
    cores = [rng.standard_normal(s) for s in ((1, 5, 2), (2, 4, 3), (3, 6, 1))]
    ar = numerics.exact()
    with ar.scope():
        got = np.asarray(tt.ttcore(ar, ar.put_index(idx), ar.put(vals), [ar.put(c) for c in cores], 1, SHAPE[1]))
    # B_1[j, (a, b)] = sum_ik X[i, j, k] G0[0, i, a] G2[b, k, 0]
    want = np.einsum("ijk,ia,bk->jab", dense, cores[0][0], cores[2][:, :, 0]).reshape(SHAPE[1], -1)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_cp_iteration_and_fit_by_hand(data):
    idx, vals, dense, rng = data
    before = {"factors": [rng.standard_normal((s, 3)) for s in SHAPE], "lam": np.ones(3)}
    ar = numerics.exact()
    with ar.scope():
        updates, state, fit = cp.iteration(ar, _x(ar, idx, vals), before)
    f = [a.copy() for a in before["factors"]]
    specs = ["ijk,jr,kr->ir", "ijk,ir,kr->jr", "ijk,ir,jr->kr"]
    for m in range(3):
        others = [f[n] for n in range(3) if n != m]
        v = (others[0].T @ others[0]) * (others[1].T @ others[1])
        a = np.linalg.solve(v + 1e-8 * np.eye(3), np.einsum(specs[m], dense, *others).T).T
        lam = np.linalg.norm(a, axis=0)
        f[m] = a / lam
        np.testing.assert_allclose(np.asarray(updates[m]["factor"]), f[m], rtol=1e-9, atol=1e-12)
    model = np.einsum("r,ir,jr,kr->ijk", lam, *f)
    want = 1 - np.linalg.norm(dense - model) / np.linalg.norm(dense)
    assert abs(fit - want) < 1e-12


def test_tucker_iteration_and_fit_by_hand(data):
    idx, vals, dense, rng = data
    ranks = (2, 3, 2)
    before = {"factors": [np.linalg.qr(rng.standard_normal((s, r)))[0] for s, r in zip(SHAPE, ranks)],
              "core": np.zeros(ranks)}
    ar = numerics.exact()
    with ar.scope():
        updates, state, fit = tucker.iteration(ar, _x(ar, idx, vals), before)
    f = list(before["factors"])
    for m, spec in enumerate(["ijk,jb,kc->ibc", "ijk,ia,kc->jac", "ijk,ia,jb->kab"]):
        y = np.einsum(spec, dense, *[f[n] for n in range(3) if n != m]).reshape(SHAPE[m], -1)
        u, sigma, _ = np.linalg.svd(y, full_matrices=False)
        u = u[:, : ranks[m]]
        got = np.asarray(updates[m]["factor"])
        assert np.allclose(got @ got.T, u @ u.T, atol=1e-9)  # the same span
        assert tucker.energy_gap(got, y, np.sum(sigma[: ranks[m]] ** 2)) < 1e-6
        f[m] = np.asarray(state["factors"][m])
    core = np.einsum("ijk,ia,jb,kc->abc", dense, *f)
    np.testing.assert_allclose(np.asarray(state["core"]), core, rtol=1e-9, atol=1e-12)
    model = np.einsum("abc,ia,jb,kc->ijk", core, *f)
    assert abs(fit - (1 - np.linalg.norm(dense - model) / np.linalg.norm(dense))) < 1e-12


def test_tt_iteration_and_fit_by_hand(data):
    idx, vals, dense, rng = data
    before = {"cores": [rng.standard_normal(s) for s in ((1, 5, 2), (2, 4, 3), (3, 6, 1))]}
    ar = numerics.exact()
    with ar.scope():
        updates, state, fit = tt.iteration(ar, _x(ar, idx, vals), before)
    g = [c.copy() for c in before["cores"]]
    for m in range(3):
        # Least squares for core m with the others fixed, solved densely.
        left = np.ones((1, 1))
        for k in range(m):
            left = np.einsum("pa,aib->pib", left, g[k]).reshape(-1, g[k].shape[2])
        right = np.ones((1, 1))
        for k in range(2, m, -1):
            right = np.einsum("aib,bq->aiq", g[k], right).reshape(g[k].shape[0], -1)
        d3 = dense.reshape(left.shape[0], SHAPE[m], right.shape[1])
        b = np.einsum("piq,pa,bq->iab", d3, left, right).reshape(SHAPE[m], -1)
        a = np.kron(left.T @ left, right @ right.T)
        a += (1e-8 * np.trace(a) / a.shape[0] + 1e-12) * np.eye(a.shape[0])
        w = np.linalg.solve(a, b.T).T
        np.testing.assert_allclose(np.asarray(updates[m]["matrix"]), w, rtol=1e-8, atol=1e-10)
        g[m] = np.transpose(w.reshape(SHAPE[m], g[m].shape[0], g[m].shape[2]), (1, 0, 2))
    model = np.einsum("aib,bjc,ckd->ijk", *g)
    assert abs(fit - (1 - np.linalg.norm(dense - model) / np.linalg.norm(dense))) < 1e-12


def test_high_arithmetic_rounds_like_three_bf16_passes():
    """The control's products carry about 16 bits: far coarser than
    float32's 24, far finer than bf16's 8."""
    rng = np.random.default_rng(1)
    a, b = rng.standard_normal(10_000), rng.standard_normal(10_000)
    hi = numerics.Arith("high", np.float32, numerics.exact().device, "high", True)
    err = np.abs(np.asarray(hi.mul(hi.put(a), hi.put(b)), np.float64) - a * b) / np.abs(a * b)
    assert 2.0**-20 < np.median(err) < 2.0**-15
    plain = np.abs(np.float32(a) * np.float32(b) - a * b) / np.abs(a * b)
    assert np.median(err) > 8 * np.median(plain)
