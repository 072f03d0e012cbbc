"""Fused dequantise -> distance -> top-k Pallas kernel (the payload-tier scan).

The tiered leaf store (DESIGN.md §3.6) keeps leaf vectors as int8 / fp16
symmetric-quantised blocks with per-block scales; stage 1 of the two-stage
search ranks the beam's leaf candidates against that quantised payload in its
*native* dtype. The win over gathering fp32 rows is pure memory traffic: the
candidate cube leaving HBM is 1 byte/element (int8) instead of 4, and the
dequantisation (one multiply by the per-row scale) happens on the VMEM tile
just before the distance reduction — the fp32 candidate cube never exists
outside VMEM.

Structurally this is ``topk.rank_pallas`` with a dequantise prologue:

  grid = (b/bq, w/bn)          # candidate axis sequential ("arbitrary")
  per step, VMEM only:
    c  = codes[bq, bn, dc] * scales[bq, bn, 1]  # unpack to planes, dequantise
    cc = sum(c*c, -1)                           # norms from dequantised planes
    d  = dist(q_planes, c)                      # VPU rowwise reduction
    state = merge_topk(state, d)                # topk.merge_topk

Only the running ``[bq, k]`` top-k state persists (in the revisited output
block); the [b, w] distance matrix never reaches HBM. Norm-consuming forms
reduce ``||c||^2`` from the dequantised tile — the quantised payload has no
fp32 norm cache by design (it would cost 4 bytes/vector, a 4/d overhead on
the tier whose whole point is ~1 byte/dim).

The contract is ``ref.scan_quantized_ref``; parity (interpret mode, vmapped
included) is ``tests/test_store.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling
from repro.kernels.ref import BIG, CODE_FORMATS, FORMS
from repro.kernels.topk import _ceil_to, _rank_tile_distance, merge_topk

Array = jax.Array


# Feature planes per packed byte: plane p of a code tile holds dimensions
# p, p + P, p + 2P, ... (int4: even / odd nibbles, binary: bit p of each byte).
PLANES = {"dense": 1, "int4": 2, "binary": 8}


def _unpack_planes(c, fmt: str, d: int):
    """In-register unpack of a packed [bq, bn, dc] code tile into planes.

    Yields ``PLANES[fmt]`` tiles of shape [bq, bn, dc]; plane ``p`` holds
    dimensions ``p, p + P, ...`` and the wrapper splits the query the same
    way, so the distance reduces plane by plane and no interleaving reshape
    (which would pad a size-2 or size-8 minor axis out to 128 lanes) runs in
    VMEM. int4: two signed nibbles per byte (branchless xor/sub sign
    extension); binary: eight sign bits per byte, mapped to ±1, with the
    padding bits past ``d`` zeroed. All arithmetic is int32 — native VPU
    ops — and HBM traffic stays at the packed width (0.5 / 0.125 bytes per
    dimension).
    """
    if fmt == "dense":
        yield c
        return
    c32 = c.astype(jnp.int32) & 0xFF
    if fmt == "int4":
        yield ((c32 & 0xF) ^ 0x8) - 0x8
        yield ((c32 >> 4) ^ 0x8) - 0x8
        return
    byte = jax.lax.broadcasted_iota(jnp.int32, c32.shape, c32.ndim - 1)
    for p in range(PLANES[fmt]):
        sign = 2 * ((c32 >> p) & 1) - 1
        yield sign if d % 8 == 0 else jnp.where(8 * byte + p < d, sign, 0)


def _scan_kernel(q_ref, c_ref, s_ref, ok_ref, od_ref, oi_ref, *, form, k, bn,
                 fmt, d):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full_like(od_ref, BIG)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    # Unpack (packed formats) + dequantise the native-dtype code tile in
    # VMEM, plane by plane: a generator, so each f32 plane is reduced into
    # the distance tile before the next one is made.
    scale = s_ref[...].astype(jnp.float32)[:, :, None]
    cs = (p.astype(jnp.float32) * scale
          for p in _unpack_planes(c_ref[...], fmt, d))
    qs = [q_ref[p] for p in range(PLANES[fmt])]
    dist = _rank_tile_distance(form, qs, cs)  # [bq, bn]
    # widen the int8 mask first: Mosaic cannot relayout an int8 compare
    # onto the reduced distance tile ("Lane broadcast")
    dist = jnp.where(ok_ref[...].astype(jnp.int32) != 0, dist, BIG)
    od_ref[...], oi_ref[...] = merge_topk(od_ref[...], oi_ref[...], dist,
                                          j * bn)


@functools.partial(
    jax.jit, static_argnames=("form", "k", "bq", "bn", "fmt", "interpret")
)
def scan_pallas(
    Q: Array,
    C: Array,
    scales: Array,
    ok: Array,
    *,
    form: str,
    k: int,
    bq: int = 8,
    bn: int = 256,
    fmt: str = "dense",
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused masked ranking of quantised per-query candidates.

    ``Q``: [b, d] f32 queries; ``C``: [b, w, dc] gathered candidate *codes*
    in the payload tier's native container — int8 / fp16 (``fmt="dense"``,
    ``dc == d``), int4 nibble pairs (``fmt="int4"``, ``dc = ceil(d/2)``) or
    packed sign bits (``fmt="binary"``, ``dc = ceil(d/8)``); ``scales``:
    [b, w] f32 per-row dequantisation scales; ``ok``: [b, w] validity mask.
    Returns (dists[b, k] ascending, slots[b, k] into the ``w`` axis); masked
    slots rank as ``BIG``. Contract: ``ref.scan_quantized_ref``.
    """
    if form not in FORMS:
        raise ValueError(f"unsupported form {form!r}")
    if fmt not in CODE_FORMATS:
        raise ValueError(f"unknown code format {fmt!r}; use {CODE_FORMATS}")
    b, d = Q.shape
    b2, w, dc = C.shape
    if b != b2:
        raise ValueError(f"shape mismatch {Q.shape} vs {C.shape}")
    if fmt == "dense" and dc != d:
        raise ValueError(f"dense codes must carry d={d}, got {dc}")
    if k > w:
        raise ValueError(f"k={k} > candidate width w={w}")

    if C.dtype == jnp.float16:
        # Mosaic loads no f16 vectors on TPU: widen fp16 codes before the
        # call (exact; the gathered cube then moves at 4 bytes per element)
        C = C.astype(jnp.float32)

    # Backend-real tiling: shrink blocks overhanging the (padded) problem
    # and bound the per-step VMEM cube (packed container + f32 unpack copy).
    bq = tiling.shrink(bq, b, tiling.sublane(jnp.float32))
    bn = tiling.shrink(bn, w, tiling.LANE)
    bn = tiling.fit_budget(
        bn,
        lambda x: tiling.vmem_rank(bq, x, dc, k, C.dtype.itemsize,
                                   PLANES[fmt]),
        floor=min(bn, tiling.LANE),
    )

    bp, wp = _ceil_to(b, bq), _ceil_to(w, bn)
    # Query planes matching the unpacked code planes: [P, bp, dc].
    n_planes = PLANES[fmt]
    Qp = jnp.pad(Q.astype(jnp.float32),
                 ((0, bp - b), (0, n_planes * dc - d)))
    Qp = Qp.reshape(bp, dc, n_planes).transpose(2, 0, 1)
    Cp = jnp.pad(C, ((0, bp - b), (0, wp - w), (0, 0)))
    Sp = jnp.pad(scales.astype(jnp.float32), ((0, bp - b), (0, wp - w)))
    okp = jnp.pad(ok.astype(jnp.int8), ((0, bp - b), (0, wp - w)))
    grid = (bp // bq, wp // bn)

    kernel = functools.partial(_scan_kernel, form=form, k=k, bn=bn, fmt=fmt,
                               d=d)
    dists, slots = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_planes, bq, dc), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bq, bn, dc), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ],
        interpret=interpret,
        name="scan_pallas",
    )(Qp, Cp, Sp, okp)
    # Same slot contract as rank_pallas: masked/short rows must not leak
    # out-of-range indices to host-side consumers.
    return dists[:b], jnp.clip(slots[:b], 0, w - 1)
