"""How a plain reference computes: its type, device and products.

`exact()` is the reference proper: float64 on the host CPU, every product
exact.  `high()` is the control of the comparison: the same reference in
float32 on the default device, with every product rounded as
`Precision.HIGH` (three bf16 passes) rounds it, the step below the float32
values and accumulation with exact products that the configurations state.  Matmuls take the
precision from `jax.default_matmul_precision`; elementwise products, which
no precision setting touches, split each operand into a bf16 head and a
bf16 tail and drop the tail-by-tail term, as the three-pass MXU product
does.

Nothing here imports the program under test.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Arith", "exact", "high", "objective_gap", "rel_max_gap"]

# Elements of one chunk's (nnz, width) contribution: bounds the reference's
# temporaries to a few hundred MB whatever the tensor.
CHUNK_ELEMENTS = 1 << 25


def _split(x):
    head = x.astype(jnp.bfloat16).astype(jnp.float32)
    tail = (x - head).astype(jnp.bfloat16).astype(jnp.float32)
    return head, tail


@dataclasses.dataclass(frozen=True)
class Arith:
    name: str
    dtype: object
    device: object
    matmul_precision: str
    three_pass: bool

    @contextlib.contextmanager
    def scope(self):
        """Run reference code in this arithmetic (64-bit types on for
        float64, the default device and matmul precision set)."""
        with contextlib.ExitStack() as stack:
            if self.dtype == jnp.float64:
                stack.enter_context(jax.enable_x64(True))
            stack.enter_context(jax.default_device(self.device))
            stack.enter_context(jax.default_matmul_precision(self.matmul_precision))
            yield self

    def put(self, x):
        """A float array in this arithmetic's type on its device."""
        return jax.device_put(jnp.asarray(np.asarray(x), self.dtype), self.device)

    def put_index(self, x):
        return jax.device_put(jnp.asarray(np.asarray(x), jnp.int32), self.device)

    def mul(self, a, b):
        """Elementwise product (broadcasting) in this arithmetic."""
        if not self.three_pass:
            return a * b
        ah, at = _split(a)
        bh, bt = _split(b)
        return ah * bh + (ah * bt + at * bh)

    def segment_sum(self, contrib, segments, rows: int, nnz: int, width: int):
        """sum over nonzeros z of contrib(z) into row segments[z], computed
        in chunks of nonzeros; `contrib(lo, hi)` gives the (hi - lo, width)
        contributions of nonzeros lo..hi-1."""
        step = max(1, CHUNK_ELEMENTS // max(1, width))
        out = jnp.zeros((rows, width), self.dtype)
        for lo in range(0, nnz, step):
            hi = min(nnz, lo + step)
            out = out + jax.ops.segment_sum(
                contrib(lo, hi), segments[lo:hi], num_segments=rows
            )
        return out

    def chunked_sum(self, term, nnz: int, width: int):
        """sum over nonzeros of term(lo, hi), a (hi - lo,) array, in chunks."""
        step = max(1, CHUNK_ELEMENTS // max(1, width))
        total = jnp.zeros((), self.dtype)
        for lo in range(0, nnz, step):
            total = total + jnp.sum(term(lo, min(nnz, lo + step)))
        return total


def exact() -> Arith:
    return Arith("float64", jnp.float64, jax.devices("cpu")[0], "highest", False)


def high() -> Arith:
    return Arith("high", jnp.float32, jax.devices()[0], "high", True)


def rel_max_gap(got, want) -> float:
    """Largest entry of |got - want| over the largest entry of |want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    return float(np.abs(got - want).max() / scale) if scale > 0 else float(np.abs(got).max())


def objective_gap(got, want, weight, *, fit_scale: bool = False) -> float:
    """How far `got` is from `want`, the minimizer of a least-squares
    update whose normal matrix is `weight` (rows solve x W = m):
    sqrt(tr((got - want) W (got - want)^T) / tr(want W want^T)), the
    update's excess objective over the fitted part, as a relative error.
    Errors along directions the data barely determines (small eigenvalues
    of W, which float32 rounding of the solve inflates) weigh little; an
    error in the contraction's output or a wrong update weighs fully.
    With `fit_scale`, each column of `got` first takes its best scale (CP
    normalizes columns, which the next update absorbs)."""
    got, want, w = (np.asarray(a, np.float64) for a in (got, want, weight))
    if fit_scale:
        p = w * (got.T @ got)
        q = np.sum(w * (got.T @ want), axis=1)
        got = got * np.linalg.lstsq(p, q, rcond=None)[0]
    d = got - want
    den = np.sum((want @ w) * want)
    return float(np.sqrt(max(np.sum((d @ w) * d), 0.0) / den)) if den > 0 else float(np.abs(got).max())
