"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package has its contract defined *here*; the Pallas
implementations are validated against these functions over shape / dtype /
distance sweeps (``tests/test_kernels.py``). These are also the CPU / small-
problem fallbacks dispatched by ``ops.py``.

Forms
-----
The kernels support the distance *forms* below (a superset of what the paper
benchmarks). ``repro.core.distances`` registry names map onto forms via
``FORM_OF``.

  sqeuclidean  ||x-y||^2            (gram / MXU)
  l2           ||x-y||              (gram / MXU)
  cosine       1 - x.y/(|x||y|)     (gram / MXU)
  dot          -x.y                 (gram / MXU)
  l1           sum|x-y|             (broadcast / VPU)
  chebyshev    max|x-y|             (broadcast / VPU)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

GRAM_FORMS = ("sqeuclidean", "l2", "cosine", "dot")
VPU_FORMS = ("l1", "chebyshev")
FORMS = GRAM_FORMS + VPU_FORMS

# registry distance name -> kernel form
FORM_OF = {
    "euclidean": "l2",
    "manhattan": "l1",
    "chebyshev": "chebyshev",
    "cosine": "cosine",
    "dot": "dot",
}

_EPS = 1e-12
BIG = 1e30
# Precision of every Gram-form dot (XLA and in-kernel). On a TPU an f32 dot
# at default precision runs as one bf16 pass; the xx + yy - 2g form then
# loses the gaps between near neighbours. HIGHEST keeps f32 inputs at f32.
PRECISION = jax.lax.Precision.HIGHEST


def _gram(X: Array, Y: Array) -> Array:
    """[m, d] x [n, d] -> [m, n] inner products at :data:`PRECISION`."""
    return jnp.matmul(X, Y.T, precision=PRECISION)


def _rowwise_gram(Q: Array, C: Array) -> Array:
    """[b, d] x [b, w, d] -> [b, w] per-query inner products."""
    return jnp.einsum("bd,bwd->bw", Q, C, preferred_element_type=jnp.float32,
                      precision=PRECISION)


def stream_cols(pairwise_fn, X: Array, Y: Array, chunk: int) -> Array:
    """Column-streamed pairwise: apply ``pairwise_fn(X, y_chunk)`` to
    [chunk]-row slabs of ``Y`` and concatenate, bounding peak memory at
    [m, chunk, d] for broadcast-form distances."""
    m, n = X.shape[0], Y.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    Yp = jnp.pad(Y, ((0, pad), (0, 0)))
    Yc = Yp.reshape(n_chunks, chunk, Y.shape[1])
    out = jax.lax.map(lambda yc: pairwise_fn(X, yc), Yc)  # [nc, m, chunk]
    return jnp.moveaxis(out, 0, 1).reshape(m, n_chunks * chunk)[:, :n]


def stream_rows(pairwise_fn, X: Array, Y: Array, chunk: int) -> Array:
    """Row-streamed pairwise: apply ``pairwise_fn(x_chunk, Y)`` to
    [chunk]-row slabs of ``X`` and stack."""
    m = X.shape[0]
    n_chunks = -(-m // chunk)
    pad = n_chunks * chunk - m
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    Xc = Xp.reshape(n_chunks, chunk, X.shape[1])
    out = jax.lax.map(lambda xc: pairwise_fn(xc, Y), Xc)  # [nc, chunk, n]
    return out.reshape(n_chunks * chunk, Y.shape[0])[:m]


def pairwise_ref_chunked(X: Array, Y: Array, form: str, chunk: int) -> Array:
    """Broadcast-form pairwise with both axes streamed: peak memory is one
    [chunk, chunk, d] slab regardless of ``m`` and ``n``."""
    m, n = X.shape[0], Y.shape[0]
    if m <= chunk and n <= chunk:
        return pairwise_ref(X, Y, form)
    if m > chunk:
        return stream_rows(
            lambda xc, Yf: pairwise_ref_chunked(xc, Yf, form, chunk), X, Y, chunk
        )
    return stream_cols(
        lambda Xf, yc: pairwise_ref(Xf, yc, form), X, Y, chunk
    )


def pairwise_ref(X: Array, Y: Array, form: str) -> Array:
    """[m, d] x [n, d] -> [m, n] distance matrix (float32 accumulate)."""
    X = X.astype(jnp.float32)
    Y = Y.astype(jnp.float32)
    if form in ("sqeuclidean", "l2"):
        xx = jnp.sum(X * X, axis=-1)
        yy = jnp.sum(Y * Y, axis=-1)
        d2 = jnp.maximum(xx[:, None] + yy[None, :] - 2.0 * _gram(X, Y), 0.0)
        return d2 if form == "sqeuclidean" else jnp.sqrt(d2)
    if form == "cosine":
        xn = jnp.sqrt(jnp.maximum(jnp.sum(X * X, axis=-1), _EPS))
        yn = jnp.sqrt(jnp.maximum(jnp.sum(Y * Y, axis=-1), _EPS))
        cos = _gram(X, Y) / (xn[:, None] * yn[None, :])
        return 1.0 - jnp.clip(cos, -1.0, 1.0)
    if form == "dot":
        return -_gram(X, Y)
    if form == "l1":
        return jnp.sum(jnp.abs(X[:, None, :] - Y[None, :, :]), axis=-1)
    if form == "chebyshev":
        return jnp.max(jnp.abs(X[:, None, :] - Y[None, :, :]), axis=-1)
    raise ValueError(f"unknown form {form!r}")


def knn_ref(Q: Array, DB: Array, k: int, form: str) -> tuple[Array, Array]:
    """Brute-force k-NN: [q, d] queries over [n, d] database.

    Returns (dists[q, k] ascending, ids[q, k]).
    """
    D = pairwise_ref(Q, DB, form)
    neg, ids = jax.lax.top_k(-D, k)
    return -neg, ids.astype(jnp.int32)


def swap_deltas_ref(
    D: Array, d1: Array, d2: Array, n1: Array, valid: Array, k: int
) -> Array:
    """FasterPAM swap-sweep ΔTD terms: ``dTD[i, j] = S[j] + T[i, j]``.

    The oracle for the fused sweep kernel (``kernels/kmedoids.py``). Inputs
    are one group's dissimilarity matrix ``D [g, g]`` plus the FasterPAM
    caches — nearest / second-nearest medoid distance ``d1/d2 [g]`` and
    nearest-medoid *slot* ``n1 [g]`` — and the validity mask. Output is the
    raw ``[k, g]`` swap-delta matrix (no medoid/validity column masking;
    callers apply it).

      S[j]    = sum_o min(D[o, j] - d1[o], 0)              (shared gain)
      T[i, j] = sum_{o: n1[o]=i, D[o, j] >= d1[o]}
                   min(d2[o], D[o, j]) - d1[o]             (removal term)

    This reference materialises the [g, g] gain / removal intermediates; the
    Pallas kernel streams them in [bg, g] row tiles so only the [k, g]
    accumulator persists. ``T`` is a row segment-sum keyed on ``n1`` — O(g²)
    adds; the kernel's one-hot matmul form (O(g²k), but MXU-shaped) computes
    the same quantity.
    """
    vf = valid.astype(jnp.float32)
    D = D.astype(jnp.float32)
    gain = jnp.minimum(D - d1[:, None], 0.0) * vf[:, None]  # [g, g]
    S = jnp.sum(gain, axis=0)  # [g]
    t = jnp.where(
        D >= d1[:, None], jnp.minimum(d2[:, None], D) - d1[:, None], 0.0
    )
    t = t * vf[:, None]  # [g, g]
    seg = jnp.where(valid, n1, k)  # invalid rows -> discarded overflow bucket
    T = jax.ops.segment_sum(t, seg, num_segments=k + 1)[:k]  # [k, g]
    return S[None, :] + T


def fold_slot_valid(cand_idx: Array, cand_ok: Array, slot_valid) -> Array:
    """Fold a per-row table validity mask into a candidate mask.

    ``slot_valid``: bool[n] over the shared point/code table (True = live) —
    the online substrate's tombstone mask (DESIGN.md §3.7). Gathers the bit
    for every candidate row and ANDs it into ``cand_ok``, so downstream
    ranking (``rank_ref`` / ``scan_quantized_ref`` / the Pallas twins) prices
    deleted rows at ``BIG`` without the table itself changing. ``None``
    passes ``cand_ok`` through untouched (the frozen-index fast path).
    """
    if slot_valid is None:
        return cand_ok
    n = slot_valid.shape[0]
    rows = jnp.clip(cand_idx, 0, n - 1)
    return cand_ok & jnp.take(slot_valid, rows)


NORM_FORMS = ("sqeuclidean", "l2", "cosine")  # forms consuming ||c||^2


def rowwise_ref(
    Q: Array, C: Array, form: str, cc: Optional[Array] = None
) -> Array:
    """Per-query candidate distances: [b, d] x [b, w, d] -> [b, w].

    The batched-beam primitive: every query carries its *own* candidate set
    (a gather of index rows), so the Gram trick becomes a batched matvec
    instead of one cross matmul. Per-element arithmetic matches
    :func:`pairwise_ref` exactly (same reduction over ``d``), which is what
    makes full-width beam search bit-compatible with the dense path.

    ``cc`` optionally supplies precomputed squared candidate norms [b, w]
    (gathered from an index-side cache); without it the norms are reduced
    from ``C`` — a full extra pass over the candidate cube.
    """
    Q = Q.astype(jnp.float32)
    C = C.astype(jnp.float32)
    if cc is None and form in NORM_FORMS:
        cc = jnp.sum(C * C, axis=-1)
    if form in ("sqeuclidean", "l2"):
        qq = jnp.sum(Q * Q, axis=-1)
        g = _rowwise_gram(Q, C)
        d2 = jnp.maximum(qq[:, None] + cc.astype(jnp.float32) - 2.0 * g, 0.0)
        return d2 if form == "sqeuclidean" else jnp.sqrt(d2)
    if form == "cosine":
        qn = jnp.sqrt(jnp.maximum(jnp.sum(Q * Q, axis=-1), _EPS))
        cn = jnp.sqrt(jnp.maximum(cc.astype(jnp.float32), _EPS))
        cos = _rowwise_gram(Q, C) / (qn[:, None] * cn)
        return 1.0 - jnp.clip(cos, -1.0, 1.0)
    if form == "dot":
        return -_rowwise_gram(Q, C)
    if form == "l1":
        return jnp.sum(jnp.abs(Q[:, None, :] - C), axis=-1)
    if form == "chebyshev":
        return jnp.max(jnp.abs(Q[:, None, :] - C), axis=-1)
    raise ValueError(f"unknown form {form!r}")


# -- packed code formats (int4 / binary payload tiers) ----------------------

CODE_FORMATS = ("dense", "int4", "binary")


def packed_width(d: int, fmt: str) -> int:
    """Packed last-axis width of a ``[.., d]`` code row in format ``fmt``."""
    if fmt == "int4":
        return -(-d // 2)
    if fmt == "binary":
        return -(-d // 8)
    return d


def pack_int4(vals: Array) -> Array:
    """Pack int4 codes two-per-byte along the last axis.

    ``vals``: [..., d] integer codes in [-8, 7]. Returns [..., ceil(d/2)]
    int8 — element ``2j`` in the low nibble of byte ``j``, ``2j+1`` in the
    high nibble (zero-padded when ``d`` is odd).
    """
    v = jnp.asarray(vals, jnp.int32)
    d = v.shape[-1]
    dc = packed_width(d, "int4")
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, 2 * dc - d)])
    pairs = v.reshape(*v.shape[:-1], dc, 2)
    lo, hi = pairs[..., 0] & 0xF, pairs[..., 1] & 0xF
    packed = (hi << 4) | lo  # 0..255
    return ((packed ^ 0x80) - 0x80).astype(jnp.int8)  # reinterpret as int8


def pack_binary(x: Array) -> Array:
    """Pack sign bits eight-per-byte along the last axis.

    ``x``: [..., d] values (or bools); bit ``j`` of byte ``i`` is
    ``x[..., 8i+j] >= 0``. Returns [..., ceil(d/8)] uint8.
    """
    x = jnp.asarray(x)
    bits = (x >= 0).astype(jnp.int32) if x.dtype != jnp.bool_ else x.astype(jnp.int32)
    d = bits.shape[-1]
    dc = packed_width(d, "binary")
    bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, 8 * dc - d)])
    groups = bits.reshape(*bits.shape[:-1], dc, 8)
    weights = jnp.left_shift(1, jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(groups * weights, axis=-1).astype(jnp.uint8)


def unpack_codes(codes: Array, fmt: str, d: int) -> Array:
    """Unpack a packed code array back to per-dimension integer codes.

    ``codes``: [..., packed_width(d, fmt)]; returns [..., d] int32 — signed
    nibbles for ``int4``, ±1 for ``binary``. ``dense`` passes through
    (int8 / fp16 codes keep their dtype). Pure jnp, branchless sign
    extension — the exact arithmetic the Pallas scan kernel inlines.
    """
    if fmt == "dense":
        return codes
    c = codes.astype(jnp.int32) & 0xFF  # byte view, container-dtype agnostic
    if fmt == "int4":
        lo = ((c & 0xF) ^ 0x8) - 0x8
        hi = ((c >> 4) ^ 0x8) - 0x8
        full = jnp.stack([lo, hi], axis=-1).reshape(*c.shape[:-1], -1)
        return full[..., :d]
    if fmt == "binary":
        shifts = jnp.arange(8, dtype=jnp.int32)
        bits = (c[..., None] >> shifts) & 1
        full = bits.reshape(*c.shape[:-1], -1)
        return (2 * full - 1)[..., :d]
    raise ValueError(f"unknown code format {fmt!r}; use {CODE_FORMATS}")


def scan_quantized_ref(
    Q: Array, C: Array, c_scales: Array, ok: Array, k: int, form: str,
    fmt: str = "dense",
) -> tuple[Array, Array]:
    """Stage-1 payload-tier scan oracle (the ``kernels/quantized.py`` contract).

    ``C``: [b, w, dc] per-query gathered *quantized* candidate codes — int8
    symmetric or fp16 for ``fmt="dense"`` (``dc == d``), two-per-byte signed
    nibbles for ``fmt="int4"`` or sign bits for ``fmt="binary"`` (``dc =
    packed_width(d, fmt)``); ``c_scales``: [b, w] per-row dequantisation
    scales (the payload tier's per-block scale broadcast to its rows).
    Candidates are unpacked (packed formats), dequantised (``code * scale``
    — binary codes dequantise to ±scale, so ``dot`` scoring is the
    asymmetric-Hamming form ``-scale * (d - 2 * hamming)`` up to the query's
    magnitudes) and ranked exactly like :func:`rank_ref`; masked slots rank
    as ``BIG``. Returns (dists[b, k] ascending, slots[b, k] into ``w``).
    """
    Cu = unpack_codes(C, fmt, Q.shape[-1])
    Cf = Cu.astype(jnp.float32) * c_scales.astype(jnp.float32)[..., None]
    D = jnp.where(ok, rowwise_ref(Q, Cf, form), BIG)
    neg, slots = jax.lax.top_k(-D, k)
    return -neg, slots.astype(jnp.int32)


def rank_ref(
    Q: Array, C: Array, ok: Array, k: int, form: str,
    cc: Optional[Array] = None,
) -> tuple[Array, Array]:
    """Masked per-query top-k over gathered candidates.

    Returns (dists[b, k] ascending, slots[b, k]) where ``slots`` index the
    candidate (``w``) axis; masked-out / missing slots yield ``BIG`` / the
    top_k tie order over ``BIG`` entries.
    """
    D = jnp.where(ok, rowwise_ref(Q, C, form, cc), BIG)
    neg, slots = jax.lax.top_k(-D, k)
    return -neg, slots.astype(jnp.int32)
