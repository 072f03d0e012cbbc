"""Tiled pairwise-distance Pallas kernels (the PDASC hot spot).

Every stage of PDASC — k-medoids BUILD/SWAP inside MSA, prototype filtering
and leaf ranking inside NSA, and the brute-force ground-truth baseline — is
dominated by ``[m, d] x [n, d] -> [m, n]`` distance matrices. The paper leaves
these to numpy on CPU; on TPU they are the MXU/VPU hot path, so this is the
kernel layer (DESIGN.md §3.3).

Two kernels, selected by distance *form* (see ``repro.kernels.ref``):

``_gram_kernel``  (sqeuclidean / l2 / cosine / dot)
    3D grid ``(m/bm, n/bn, d/bd)``; each step does one ``[bm, bd] @ [bd, bn]``
    MXU matmul accumulated in an f32 VMEM scratch tile. The distance epilogue
    (norm combination, sqrt, clipping) runs once on the final ``d`` step.
    Row norms are precomputed outside (O(nd), memory-light) and streamed in as
    ``[*, 1]`` blocks.

``_vpu_kernel``  (l1 / chebyshev)
    Same grid; no matmul form exists, so each step materialises the
    ``[bm, bn, bd]`` difference cube *in VMEM only* (never HBM) and reduces it
    on the VPU. The row tile ``bm`` shrinks until the cube fits VMEM.

Both kernels accumulate in f32 regardless of input dtype (bf16 inputs hit the
MXU natively in the gram path). Grid dims are ``(parallel, parallel,
arbitrary)`` — XLA may shard the first two freely; the ``d`` dim carries the
accumulator.

Zero-padding correctness: zero-padded ``d`` contributes 0 to every form;
padded rows/cols are sliced off by the ``ops.py`` wrapper (cosine guards the
0-norm padding rows with ``eps``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.ref import FORMS, GRAM_FORMS, PRECISION

Array = jax.Array

_EPS = 1e-12


def _gram_epilogue(form: str, g: Array, xx: Array, yy: Array) -> Array:
    """Turn an accumulated Gram tile into the requested distance tile."""
    if form == "dot":
        return -g
    if form in ("sqeuclidean", "l2"):
        d2 = jnp.maximum(xx + yy - 2.0 * g, 0.0)
        return d2 if form == "sqeuclidean" else jnp.sqrt(d2)
    if form == "cosine":
        norm = jnp.sqrt(jnp.maximum(xx, _EPS)) * jnp.sqrt(jnp.maximum(yy, _EPS))
        return 1.0 - jnp.clip(g / norm, -1.0, 1.0)
    raise ValueError(form)


def _gram_kernel(x_ref, y_ref, xx_ref, yy_ref, o_ref, acc_ref, *, form, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x, y = x_ref[...], y_ref[...]
    # f32 tiles take the full-precision passes; bf16 products are exact in
    # f32 already, and Mosaic refuses an fp32 contract precision for them.
    prec = PRECISION if x.dtype == jnp.float32 else None
    acc_ref[...] += jnp.dot(
        x, y.T, preferred_element_type=jnp.float32, precision=prec
    )

    @pl.when(kk == nk - 1)
    def _epilogue():
        xx = xx_ref[...].astype(jnp.float32)  # [bm, 1]
        yy = yy_ref[...].astype(jnp.float32)  # [bn, 1]
        o_ref[...] = _gram_epilogue(form, acc_ref[...], xx, yy.T).astype(
            o_ref.dtype
        )


def _vpu_kernel(x_ref, y_ref, o_ref, acc_ref, *, form, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    diff = jnp.abs(
        x_ref[...].astype(jnp.float32)[:, None, :]
        - y_ref[...].astype(jnp.float32)[None, :, :]
    )  # [bm, bn, bd] — VMEM-resident cube
    if form == "l1":
        acc_ref[...] += jnp.sum(diff, axis=-1)
    else:  # chebyshev; abs >= 0 so the zero init is the identity
        acc_ref[...] = jnp.maximum(acc_ref[...], jnp.max(diff, axis=-1))

    @pl.when(kk == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad2(a: Array, m: int, n: int) -> Array:
    return jnp.pad(a, ((0, m - a.shape[0]), (0, n - a.shape[1])))


@functools.partial(
    jax.jit, static_argnames=("form", "bm", "bn", "bd", "interpret", "out_dtype")
)
def pairwise_pallas(
    X: Array,
    Y: Array,
    *,
    form: str,
    bm: int = 128,
    bn: int = 128,
    bd: int = 256,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> Array:
    """Tiled ``[m, d] x [n, d] -> [m, n]`` distance matrix.

    Pads every axis up to its block multiple; callers slice ``[:m, :n]``
    (``ops.pairwise_distance`` does). ``form`` is one of ``ref.FORMS``.
    """
    if form not in FORMS:
        raise ValueError(f"unsupported form {form!r}; kernels support {FORMS}")
    m, d = X.shape
    n, d2 = Y.shape
    if d != d2:
        raise ValueError(f"dim mismatch {d} vs {d2}")

    # Backend-real tiling: align the d (lane) axis and the m (sublane) axis
    # to the input dtype's tile multiples and shrink blocks overhanging the
    # (padded) problem. ``bd`` is the lane axis of both input tiles, so it
    # stays a multiple of 128 (or the whole padded d); the per-step VMEM
    # budget — which the VPU forms' [bm, bn, bd] difference cube must fit —
    # is met by halving the row tile ``bm`` (then ``bn``) instead.
    isize = X.dtype.itemsize
    sub = tiling.sublane(X.dtype)
    bm = tiling.shrink(bm, m, sub)
    bn = tiling.shrink(bn, n, tiling.LANE)
    bd = tiling.shrink(bd, d, tiling.LANE)
    bm = tiling.fit_budget(
        bm, lambda x: tiling.vmem_pairwise(form, x, bn, bd, isize),
        floor=min(bm, sub),
    )
    bn = tiling.fit_budget(
        bn, lambda x: tiling.vmem_pairwise(form, bm, x, bd, isize),
        floor=min(bn, tiling.LANE),
    )

    mp, np_, dp = _ceil_to(m, bm), _ceil_to(n, bn), _ceil_to(d, bd)
    Xp = _pad2(X, mp, dp)
    Yp = _pad2(Y, np_, dp)
    gm, gn, gk = mp // bm, np_ // bn, dp // bd
    grid = (gm, gn, gk)
    out_shape = jax.ShapeDtypeStruct((mp, np_), out_dtype)
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]

    if form in GRAM_FORMS:
        Xf = Xp.astype(jnp.float32)
        Yf = Yp.astype(jnp.float32)
        xx = jnp.sum(Xf * Xf, axis=1, keepdims=True)  # [mp, 1]
        yy = jnp.sum(Yf * Yf, axis=1, keepdims=True)  # [np, 1]
        kernel = functools.partial(_gram_kernel, form=form, nk=gk)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),
                pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
                pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
                pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0)),
            ],
            out_specs=out_spec,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="pairwise_pallas",
        )(Xp, Yp, xx, yy)

    kernel = functools.partial(_vpu_kernel, form=form, nk=gk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="pairwise_pallas",
    )(Xp, Yp)
