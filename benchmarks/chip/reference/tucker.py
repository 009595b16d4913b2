"""Plain Tucker-HOOI reference: one iteration, and the comparison with the
program's.

The iteration follows the algorithm the program states (HOOI, modes in
order, each update seeing the modes already updated):

    Y_m = X_(m) (Kronecker product of the other factors, ascending modes)
    U_m = the top R_m left singular vectors of Y_m
    G   = U_last^T Y_last, folded into (R_0, ..., R_{N-1})
    fit = 1 - ||X - G x_1 U_1 ... x_N U_N|| / ||X||

Singular vectors are fixed only up to sign and, between equal singular
values, rotation, so a factor is judged by what HOOI asks of it: the share
of Y_m's best rank-R_m energy that its span misses (`energy_gap`).  `compare` is teacher-forced as in
the CP reference; the core is judged against U_last^T Y_last with the
program's own U_last, which fixes the signs, and the fit is the float64 fit
of the model the program returned, not the program's shortcut formula.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from .numerics import rel_max_gap


def ttmc(ar, idx, vals, factors, mode, rows):
    width = math.prod(f.shape[1] for n, f in enumerate(factors) if n != mode)

    def contrib(lo, hi):
        p = vals[lo:hi, None]
        for n, f in enumerate(factors):
            if n != mode:
                p = ar.mul(p[:, :, None], f[idx[lo:hi, n]][:, None, :]).reshape(hi - lo, -1)
        return p

    return ar.segment_sum(contrib, idx[:, mode], rows, idx.shape[0], width)


def _fold_core(mat, mode, ranks):
    """(R_mode, prod of the other ranks) -> the core in natural mode order."""
    others = tuple(m for m in range(len(ranks)) if m != mode)
    core = mat.reshape((ranks[mode],) + tuple(ranks[m] for m in others))
    axes = (mode,) + others
    return jnp.transpose(core, tuple(axes.index(m) for m in range(len(ranks))))


def fit(ar, x, factors, core) -> float:
    idx, vals, norm_x_sq = x["idx"], x["vals"], x["norm_x_sq"]
    g = core.reshape(-1)

    def term(lo, hi):
        p = vals[lo:hi, None]
        for n, f in enumerate(factors):
            p = ar.mul(p[:, :, None], f[idx[lo:hi, n]][:, None, :]).reshape(hi - lo, -1)
        return p @ g

    inner = ar.chunked_sum(term, idx.shape[0], g.shape[0])
    t = core
    for n, f in enumerate(factors):
        t = jnp.moveaxis(jnp.tensordot(f.T @ f, t, axes=([1], [n])), 0, n)
    model_sq = jnp.sum(core * t)
    resid = jnp.maximum(norm_x_sq + model_sq - 2.0 * inner, 0.0)
    return float(1.0 - jnp.sqrt(resid) / jnp.sqrt(norm_x_sq))


def iteration(ar, x, before, forced=None):
    """One HOOI iteration from `before` ({"factors", "core"}).  Returns
    (each mode's new factor as this arithmetic computes it, the state
    after, its fit).  With `forced`, the iteration carries on from
    forced["factors"][m] after computing mode m's factor."""
    factors = [ar.put(f) for f in before["factors"]]
    ranks = tuple(f.shape[1] for f in factors)
    updates, y = [], None
    for m in range(len(factors)):
        y = ttmc(ar, x["idx"], x["vals"], factors, m, factors[m].shape[0])
        u, sigma, _ = jnp.linalg.svd(y, full_matrices=False)
        u = u[:, : ranks[m]]
        updates.append({"factor": u, "unfolding": y, "energy": jnp.sum(sigma[: ranks[m]] ** 2)})
        factors[m] = ar.put(forced["factors"][m]) if forced else u
    last = len(factors) - 1
    core = _fold_core(factors[last].T @ y, last, ranks)
    updates.append({"core": core})
    if forced:
        core = ar.put(forced["core"])
    return updates, {"factors": factors, "core": core}, fit(ar, x, factors, core)


def energy_gap(u, y, energy) -> float:
    """sqrt of the share of the best rank-R energy of Y (its top singular
    values squared) that span(u) misses: sqrt(1 - ||P_u Y||^2 / energy).
    It grows with the square of a subspace's angle to the best one, and
    directions between near-equal singular values, which rounding turns
    freely, cost next to nothing."""
    u, y = np.asarray(u, np.float64), np.asarray(y, np.float64)
    q = np.linalg.qr(u)[0]
    captured = np.sum((q.T @ y) ** 2)
    return float(np.sqrt(max(0.0, 1.0 - captured / float(energy))))


def compare(ar, x, before, after, reported_fit) -> dict:
    updates, _, ref_fit = iteration(ar, x, before, forced=after)
    return {
        "update_gap": max(energy_gap(a, u["unfolding"], u["energy"])
                          for a, u in zip(after["factors"], updates[:-1])),
        "core_gap": rel_max_gap(after["core"], updates[-1]["core"]),
        "fit_gap": abs(float(reported_fit) - ref_fit),
    }
