"""Plain CP-ALS reference: one iteration, and the comparison with the
program's.

The iteration follows the algorithm the program states (Kolda and Bader's
CP-ALS, modes in order, each update seeing the modes already updated):

    M_m = X_(m) (Khatri-Rao product of the other factors)   the MTTKRP
    V_m = Hadamard product of F_n^T F_n over n != m
    F_m = M_m (V_m + 1e-8 I)^-1, columns scaled to unit 2-norm, lam = norms
    fit = 1 - ||X - [[lam; F]]|| / ||X||

The 1e-8 ridge is the program's stated guard and changes nothing at these
scales; the first iteration's max(norm, 1) convention never comes up, as
the check replays a later iteration.

`compare` is teacher-forced: mode m's update is computed from the
program's own factors (its new ones for modes before m, its incoming ones
after), so each mode's kernel output and solve is judged alone, and the fit
is the float64 fit of the very model the program returned.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .numerics import objective_gap, rel_max_gap

RIDGE = 1e-8


def mttkrp(ar, idx, vals, factors, mode, rows):
    r = factors[0].shape[1]

    def contrib(lo, hi):
        p = vals[lo:hi, None]
        for n, f in enumerate(factors):
            if n != mode:
                p = ar.mul(p, f[idx[lo:hi, n]])
        return p

    return ar.segment_sum(contrib, idx[:, mode], rows, idx.shape[0], r)


def _gram_hadamard(factors, skip):
    g = None
    for n, f in enumerate(factors):
        if n != skip:
            gn = f.T @ f
            g = gn if g is None else g * gn
    return g


def fit(ar, x, factors, lam) -> float:
    idx, vals, norm_x_sq = x["idx"], x["vals"], x["norm_x_sq"]
    r = lam.shape[0]

    def term(lo, hi):
        p = vals[lo:hi, None]
        for n, f in enumerate(factors):
            p = ar.mul(p, f[idx[lo:hi, n]])
        return p @ lam

    inner = ar.chunked_sum(term, idx.shape[0], r)
    model_sq = lam @ _gram_hadamard(factors, -1) @ lam
    resid = jnp.maximum(norm_x_sq + model_sq - 2.0 * inner, 0.0)
    return float(1.0 - jnp.sqrt(resid) / jnp.sqrt(norm_x_sq))


def iteration(ar, x, before, forced=None):
    """One ALS iteration from the state `before` ({"factors", "lam"}).
    Returns (each mode's update as this arithmetic computes it, the state
    after the iteration, its fit).  With `forced`, the iteration carries on
    from forced["factors"][m] after computing mode m's update."""
    factors = [ar.put(f) for f in before["factors"]]
    rows = [f.shape[0] for f in factors]
    updates, lam = [], None
    for m in range(len(factors)):
        mt = mttkrp(ar, x["idx"], x["vals"], factors, m, rows[m])
        v = _gram_hadamard(factors, m)
        v = v + RIDGE * jnp.eye(v.shape[0], dtype=v.dtype)
        solved = jax.scipy.linalg.solve(v, mt.T, assume_a="pos").T
        norms = jnp.linalg.norm(solved, axis=0)
        norms = jnp.where(norms > 1e-12, norms, 1.0)
        f, lam = solved / norms, norms
        updates.append({"factor": f, "lam": lam, "solved": solved, "normal": v})
        factors[m] = ar.put(forced["factors"][m]) if forced else f
    if forced:
        lam = ar.put(forced["lam"])
    return updates, {"factors": factors, "lam": lam}, fit(ar, x, factors, lam)


def compare(ar, x, before, after, reported_fit) -> dict:
    """The numbers by which `after` (the program's state after one
    iteration from `before`, and the fit it reported) departs from the
    reference, worst mode first."""
    updates, _, ref_fit = iteration(ar, x, before, forced=after)
    return {
        "update_gap": max(objective_gap(a, u["solved"], u["normal"], fit_scale=True)
                          for a, u in zip(after["factors"], updates)),
        "lam_gap": rel_max_gap(after["lam"], updates[-1]["lam"]),
        "fit_gap": abs(float(reported_fit) - ref_fit),
    }
