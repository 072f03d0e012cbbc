"""Plain exact k-nearest-neighbour reference, computed in blocks on the device.

Imports nothing of the program under test. Two passes:

1. top-k candidates by the Gram form (``q @ x.T`` at a stated precision,
   squared norms by elementwise sums) over database blocks, carried through
   a ``lax.scan`` so that only one [queries, block] matrix lives at a time;
2. the candidates' distances again by the distance's direct form
   (subtraction or elementwise products, no matmul), sorted.

``precision="highest"`` is the reference. ``"bf16_3x"`` is the control: the
same pass computed the way ``Precision.HIGH`` computes a float32 product on
a TPU (three bf16 products, the low-by-low term dropped), written out with
``lax.reduce_precision`` so that it reads the same on every platform (a
pair of converts would not do: the TPU compiler may drop a float32 ->
bfloat16 -> float32 round trip as excess precision). ``"high"`` is the
platform's own ``Precision.HIGH``, which only a TPU honours.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bf16_3x", "high")


def _to_bf16(x):
    """Round to bfloat16's 8 exponent and 7 mantissa bits, kept in f32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split_bf16(x):
    hi = _to_bf16(x)
    return hi, _to_bf16(x - hi)


def gram(q, x, precision: str):
    """q [m, d] @ x.T [d, n] at ``precision``."""
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.dot(q, x.T, precision=hp)
    if precision == "high":
        return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGH)
    if precision == "bf16_3x":
        qh, ql = _split_bf16(q)
        xh, xl = _split_bf16(x)
        # bf16 x bf16 products are exact in float32; HIGHEST keeps them so
        return (jnp.dot(qh, xh.T, precision=hp) + jnp.dot(qh, xl.T, precision=hp)
                + jnp.dot(ql, xh.T, precision=hp))
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("dist", "k", "precision"))
def _topk_block(q, xb, xxb, okb, offb, *, dist, k, precision):
    """Top-k over database blocks ``xb`` [nb, bx, d] for queries ``q``."""
    qq = jnp.sum(q * q, axis=-1)
    m = q.shape[0]

    def step(carry, blk):
        best_d, best_i = carry
        x, xx, ok, off = blk
        d = dist.from_gram(gram(q, x, precision), qq, xx)
        d = jnp.where(ok[None, :], d, jnp.inf)
        ids = off + jnp.arange(x.shape[0], dtype=jnp.int32)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids[None, :], d.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(step, init, (xb, xxb, okb, offb))
    return best_d, best_i


class Database:
    """The database on the device, cut into ``block``-row blocks."""

    def __init__(self, X: np.ndarray, dist, *, block: int = 32768):
        n, d = X.shape
        self.n, self.d, self.dist = n, d, dist
        nb = -(-n // block)
        pad = nb * block - n
        self.rows = jnp.asarray(X)
        xp = jnp.pad(self.rows, ((0, pad), (0, 0)))
        self.xb = xp.reshape(nb, block, d)
        self.xxb = jnp.sum(self.xb * self.xb, axis=-1)
        self.okb = (jnp.arange(nb * block) < n).reshape(nb, block)
        self.offb = jnp.arange(nb, dtype=jnp.int32) * block

    def topk(self, Q, k: int, *, precision: str = "highest",
             qblock: int = 512):
        """(Gram-form distances [q, k] ascending, ids [q, k])."""
        out_d, out_i = [], []
        for s in range(0, len(Q), qblock):
            q = jnp.asarray(Q[s:s + qblock], jnp.float32)
            bd, bi = _topk_block(q, self.xb, self.xxb, self.okb, self.offb,
                                 dist=self.dist, k=k, precision=precision)
            out_d.append(np.asarray(bd))
            out_i.append(np.asarray(bi))
        return np.concatenate(out_d), np.concatenate(out_i)

    def direct(self, Q, ids, *, block: int = 4096):
        """Direct-form distance of each id [m, j] from its query row
        ``Q`` [m, d]; NaN where an id is outside [0, n)."""
        ids = np.asarray(ids)
        ok = (ids >= 0) & (ids < self.n)
        out = np.empty(ids.shape, np.float64)
        for s in range(0, len(ids), block):
            sl = slice(s, s + block)
            safe = jnp.asarray(np.where(ok[sl], ids[sl], 0).astype(np.int32))
            c = jnp.take(self.rows, safe, axis=0)
            out[sl] = np.asarray(
                self.dist.direct(jnp.asarray(Q[sl], jnp.float32), c))
        return np.where(ok, out, np.nan)

    def neighbours(self, Q, k: int, *, spare: int = 6):
        """Exact k nearest neighbours: (direct distances [q, k] ascending,
        ids [q, k]). ``spare`` extra Gram-form candidates absorb the Gram
        form's rounding at the k-th place."""
        _, cand = self.topk(Q, k + spare)
        dd = self.direct(Q, cand)
        order = np.argsort(dd, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(dd, order, axis=1),
                np.take_along_axis(cand, order, axis=1))
