"""Plain TT-ALS reference: one left-to-right sweep, and the comparison with
the program's.

Cores G_k are (rl_k, I_k, rr_k), boundary ranks 1.  The sweep follows the
algorithm the program states (single-site ALS, left to right):

    B_m = sum_z v_z kron(l_z, r_z) into row i_m(z)   the TT-core kernel
          (l_z: the left cores' slices chained, r_z: the right cores')
    P   = left interface Gram of the cores before m (already updated)
    Q   = right interface Gram of the cores after m (as the sweep began)
    W_m = B_m (kron(P, Q) + ridge I)^-1,  G_m = W_m folded to (rl, I, rr)
    fit = 1 - ||X - TT|| / ||X||

The ridge, 1e-8 of the mean diagonal plus 1e-12, is the program's stated
guard.  `compare` is teacher-forced as in the CP reference, and the fit is
the float64 fit of the cores the program returned.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .numerics import objective_gap


def to_matrix(core):
    rl, i, rr = core.shape
    return jnp.transpose(core, (1, 0, 2)).reshape(i, rl * rr)


def to_core(w, rl, rr):
    return jnp.transpose(w.reshape(w.shape[0], rl, rr), (1, 0, 2))


def _slices(core, idx):
    return jnp.transpose(core, (1, 0, 2))[idx]  # (z, rl, rr)


def ttcore(ar, idx, vals, cores, mode, rows):
    rl, rr = cores[mode].shape[0], cores[mode].shape[2]

    def contrib(lo, hi):
        sl = idx[lo:hi]
        left = jnp.ones((hi - lo, 1), ar.dtype)
        for k in range(mode):
            left = jnp.einsum("za,zab->zb", left, _slices(cores[k], sl[:, k]))
        right = jnp.ones((hi - lo, 1), ar.dtype)
        for k in range(len(cores) - 1, mode, -1):
            right = jnp.einsum("zab,zb->za", _slices(cores[k], sl[:, k]), right)
        outer = ar.mul(left[:, :, None], right[:, None, :]).reshape(hi - lo, -1)
        return ar.mul(vals[lo:hi, None], outer)

    return ar.segment_sum(contrib, idx[:, mode], rows, idx.shape[0], rl * rr)


def _p_next(p, core):
    return jnp.einsum("aib,ac,cid->bd", core, p, core)


def _q_prev(q, core):
    return jnp.einsum("aib,bc,dic->ad", core, q, core)


def fit(ar, x, cores) -> float:
    idx, vals, norm_x_sq = x["idx"], x["vals"], x["norm_x_sq"]
    width = max(c.shape[0] * c.shape[2] for c in cores)

    def term(lo, hi):
        v = jnp.ones((hi - lo, 1), ar.dtype)
        for k, core in enumerate(cores):
            v = jnp.einsum("za,zab->zb", v, _slices(core, idx[lo:hi, k]))
        return ar.mul(vals[lo:hi], v[:, 0])

    inner = ar.chunked_sum(term, idx.shape[0], width)
    p = jnp.ones((1, 1), ar.dtype)
    for core in cores:
        p = _p_next(p, core)
    resid = jnp.maximum(norm_x_sq + p[0, 0] - 2.0 * inner, 0.0)
    return float(1.0 - jnp.sqrt(resid) / jnp.sqrt(norm_x_sq))


def iteration(ar, x, before, forced=None):
    """One left-to-right sweep from `before` ({"cores"}).  Returns (each
    mode's new interface matrix W_m as this arithmetic computes it, the
    state after, its fit).  With `forced`, the sweep carries on from
    forced["cores"][m] after computing mode m's update."""
    cores = [ar.put(c) for c in before["cores"]]
    n = len(cores)
    qs, q = [None] * n, jnp.ones((1, 1), ar.dtype)
    for m in range(n - 1, -1, -1):
        qs[m] = q
        q = _q_prev(q, cores[m])
    p = jnp.ones((1, 1), ar.dtype)
    updates = []
    for m in range(n):
        rl, rows, rr = cores[m].shape
        b = ttcore(ar, x["idx"], x["vals"], cores, m, rows)
        a = jnp.kron(p, qs[m])
        dim = a.shape[0]
        a = a + (1e-8 * jnp.trace(a) / dim + 1e-12) * jnp.eye(dim, dtype=a.dtype)
        w = jax.scipy.linalg.solve(a, b.T, assume_a="pos").T
        updates.append({"matrix": w, "normal": a})
        cores[m] = ar.put(forced["cores"][m]) if forced else to_core(w, rl, rr)
        p = _p_next(p, cores[m])
    return updates, {"cores": cores}, fit(ar, x, cores)


def compare(ar, x, before, after, reported_fit) -> dict:
    updates, _, ref_fit = iteration(ar, x, before, forced=after)
    mats = [to_matrix(jnp.asarray(c)) for c in after["cores"]]
    return {
        "update_gap": max(objective_gap(w, u["matrix"], u["normal"]) for w, u in zip(mats, updates)),
        "fit_gap": abs(float(reported_fit) - ref_fit),
    }
