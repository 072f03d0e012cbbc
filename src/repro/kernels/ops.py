"""jit'd dispatch wrappers over the Pallas kernels.

Public ops (the single execution substrate for every NSA/MSA distance
evaluation and ranking step — DESIGN.md §3.3):

  pairwise_distance(X, Y, distance)       -> [m, n]
  knn(Q, DB, distance, k)                 -> (dists[q, k], ids[q, k])
  rank_candidates(Q, C, ok, distance, k)  -> (dists[b, k], slots[b, k])
  swap_deltas(D, d1, d2, n1, valid, k)    -> [k, g]  (k-medoids swap sweep)
  scan_quantized(Q, codes, scales, idx, ok, distance, k)
                                          -> (dists[b, k], slots[b, k])
                                             (quantised payload-tier scan;
                                             dense int8/fp16 or packed
                                             int4/binary codes)

``distance`` may be a kernel form (``ref.FORMS``), a registry name
(``repro.core.distances``), or a ``Distance`` object. Dispatch:

* TPU backend            -> compiled Pallas kernel.
* CPU/GPU + small input  -> pure-jnp reference (fast enough, no interpreter).
* CPU + ``force_pallas`` -> Pallas ``interpret=True`` (used by tests to
  execute the kernel body on this container).
* form not kernelised (haversine, jaccard, fractional, generic minkowski)
  -> reference / registry fallback. PDASC stays fully functional for *any*
  distance; the kernels accelerate the common forms.

``KernelConfig`` bundles the block-size knobs (``bm/bn/bd`` for the pairwise
grid, ``bq`` for the query tile of the fused rank/knn kernels, ``row_chunk``
for the CPU streaming fallbacks) so callers can thread one hashable object
through jit'd search functions. Block resolution per op (DESIGN.md §3.9):

  explicit call knob  >  non-default ``KernelConfig`` field  >
  autotuned winner (``auto=True``, ``kernels/autotune.py`` cache lookup)  >
  ``KernelConfig`` field  >  per-op hand-set default (``tiling.OP_DEFAULTS``)

so explicit knobs always win, a threaded config behaves exactly as before,
and ``KernelConfig(auto=True)`` transparently picks tuned blocks for the
fields left at their defaults. The lookup is a host-side dict read — safe at
trace time; ``tuned_gen`` (stamped by the plan compiler from
``autotune.generation()``) makes a jitted search retrace when the winners
change, since the config is a static argument.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout

from repro.kernels import autotune as _at
from repro.kernels import kmedoids as _kmk
from repro.kernels import pairwise as _pw
from repro.kernels import quantized as _qk
from repro.kernels import ref as _ref
from repro.kernels import tiling as _tiling
from repro.kernels import topk as _tk

Array = jax.Array


class KernelConfig(NamedTuple):
    """Block-size knobs for the kernel layer (hashable; jit-static)."""

    bm: int = 128  # pairwise: query-rows tile
    bn: int = 128  # pairwise / rank / knn: candidate-cols tile
    bd: int = 256  # pairwise: feature-dim tile (VMEM-budget fit per dtype)
    bq: int = 8  # rank / knn: query tile of the fused top-k kernels
    bg: int = 128  # swap sweep: point-rows tile of the fused sweep kernel
    row_chunk: int = 1024  # CPU fallback streaming chunk (bounds cube memory)
    group_chunk: int = 8  # MSA build: groups clustered per streamed slab
    force_pallas: bool = False  # run Pallas interpret=True off-TPU (tests)
    auto: bool = False  # resolve default-valued knobs from the tuner cache
    tuned_gen: int = -1  # autotune generation stamped by the plan compiler


DEFAULT = KernelConfig()


def resolve_form(distance) -> Optional[str]:
    """Best-effort map of a distance spec to a kernel form (None = no kernel)."""
    if isinstance(distance, str):
        if distance in _ref.FORMS:
            return distance
        return _ref.FORM_OF.get(distance)
    name = getattr(distance, "name", None)
    return _ref.FORM_OF.get(name) if name else None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _layout_device():
    """The device whose default layouts a traced program receives."""
    return jax.devices()[0]


def column_major(table) -> bool:
    """Whether the backend stores a 2-D table of this shape column-major.

    A jitted search receives each index table in the backend's default
    layout for its shape. The TPU picks the tighter tiling: ``f32[n, 100]``
    is stored column-major (100 features pad to 104 sublanes: 416 B a row,
    against 512 B row-major), while ``f32[n, 256]`` and tables of a few
    hundred rows are stored row-major. Backends without layouts (and the
    CPU) read as row-major.
    """
    if table.ndim != 2:
        return False
    dev = _layout_device()
    try:
        pjrt = dev.client.get_default_layout(
            np.dtype(table.dtype), tuple(table.shape), dev)
    except jax.errors.JaxRuntimeError:  # backend without layout queries
        return False
    return Layout.from_pjrt_layout(pjrt).major_to_minor == (1, 0)


def _fp(force_pallas: Optional[bool], config: Optional[KernelConfig]) -> bool:
    if force_pallas is not None:
        return force_pallas
    return config.force_pallas if config is not None else False


def resolve_blocks(
    op: str,
    form: Optional[str],
    dtype: str,
    shape,
    config: Optional[KernelConfig] = None,
    **explicit,
) -> dict:
    """Resolve one op's block knobs (the precedence chain in the module doc).

    ``explicit`` carries the per-call knob arguments (None = unset). A
    config field counts as explicitly set when it differs from the
    ``KernelConfig`` class default — the documented heuristic that lets
    ``auto=True`` fill only the knobs the caller left alone.
    """
    tuned = None
    if config is not None and config.auto:
        tuned = _at.lookup(op=op, form=form or "none", dtype=dtype,
                           shape=shape)
    out = {}
    for knob, hand_default in _tiling.OP_DEFAULTS[op].items():
        exp = explicit.get(knob)
        if exp is not None:
            out[knob] = int(exp)
        elif config is not None and \
                getattr(config, knob) != getattr(DEFAULT, knob):
            out[knob] = getattr(config, knob)
        elif tuned is not None and knob in tuned:
            out[knob] = int(tuned[knob])
        elif config is not None:
            out[knob] = getattr(config, knob)
        else:
            out[knob] = hand_default
    return out


def pairwise_distance(
    X: Array,
    Y: Array,
    distance="l2",
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bd: Optional[int] = None,
    row_chunk: Optional[int] = None,
    force_pallas: Optional[bool] = None,
    config: Optional[KernelConfig] = None,
) -> Array:
    """[m, d] x [n, d] -> [m, n] distances via the best available path.

    ``row_chunk`` bounds the peak memory of the non-Gram CPU fallbacks: the
    broadcast cube is streamed in slabs of at most [row_chunk, row_chunk, d]
    (both axes chunked) instead of being materialised whole. The Pallas
    paths tile through VMEM and never build the cube regardless.
    """
    fp = _fp(force_pallas, config)
    if row_chunk is None and config is not None:
        row_chunk = config.row_chunk
    form = resolve_form(distance)
    if form is None:
        from repro.core import distances as dist_lib  # registry fallback

        return dist_lib.pairwise_chunked(
            distance, X, Y, chunk=row_chunk or 4096
        )
    m, n = X.shape[0], Y.shape[0]
    if _on_tpu() or fp:
        knobs = resolve_blocks(
            "pairwise", form, str(X.dtype), (m, n, X.shape[1]), config,
            bm=bm, bn=bn, bd=bd,
        )
        out = _pw.pairwise_pallas(
            X, Y, form=form, interpret=not _on_tpu(), **knobs
        )
        return out[:m, :n]
    if form in _ref.VPU_FORMS and row_chunk and (m > row_chunk or n > row_chunk):
        return _ref.pairwise_ref_chunked(X, Y, form, row_chunk)
    return _ref.pairwise_ref(X, Y, form)


def knn(
    Q: Array,
    DB: Array,
    distance="l2",
    *,
    k: int = 10,
    bq: Optional[int] = None,
    bn: Optional[int] = None,
    force_pallas: Optional[bool] = None,
    config: Optional[KernelConfig] = None,
) -> tuple[Array, Array]:
    """Fused brute-force k-NN (ascending dists, int32 ids)."""
    fp = _fp(force_pallas, config)
    form = resolve_form(distance)
    if form is None:
        from repro.core import distances as dist_lib

        D = dist_lib.pairwise_chunked(distance, Q, DB)
        neg, ids = jax.lax.top_k(-D, k)
        return -neg, ids.astype(jnp.int32)
    if _on_tpu() or fp:
        knobs = resolve_blocks(
            "knn", form, str(Q.dtype),
            (Q.shape[0], DB.shape[0], Q.shape[1]), config, bq=bq, bn=bn,
        )
        return _tk.knn_pallas(
            Q, DB, form=form, k=k, interpret=not _on_tpu(), **knobs
        )
    return _ref.knn_ref(Q, DB, k, form)


def rank_candidates(
    Q: Array,
    C: Array,
    ok: Array,
    distance="l2",
    *,
    k: int,
    c_sq_norms: Optional[Array] = None,
    bq: Optional[int] = None,
    bn: Optional[int] = None,
    force_pallas: Optional[bool] = None,
    config: Optional[KernelConfig] = None,
) -> tuple[Array, Array]:
    """Fused masked ranking of per-query gathered candidates.

    ``Q``: [b, d]; ``C``: [b, w, d]; ``ok``: [b, w] bool. Returns
    (dists[b, k] ascending, slots[b, k] indexing the ``w`` axis). Masked /
    missing slots rank as ``BIG``. This is the batched-beam primitive: one
    call replaces ``b`` independent scalar gather+top_k searches, and on the
    Pallas paths the [b, w] distance matrix never leaves VMEM.

    ``c_sq_norms``: optional [b, w] squared candidate norms gathered from an
    index-side cache (``PDASCLevel.sq_norm``). For the norm-consuming forms
    this saves a full reduction pass over the [b, w, d] candidate cube.
    """
    fp = _fp(force_pallas, config)
    form = resolve_form(distance)
    if form is None:
        from repro.core import distances as dist_lib

        dist = dist_lib.get(distance)
        D = dist.point(Q[:, None, :], C)  # broadcast over the w axis
        D = jnp.where(ok, D, dist_lib.BIG)
        neg, slots = jax.lax.top_k(-D, k)
        return -neg, slots.astype(jnp.int32)
    if _on_tpu() or fp:
        knobs = resolve_blocks(
            "rank", form, str(C.dtype), C.shape, config, bq=bq, bn=bn,
        )
        return _tk.rank_pallas(
            Q, C, ok, c_sq_norms,
            form=form, k=k, interpret=not _on_tpu(), **knobs,
        )
    return _ref.rank_ref(Q, C, ok, k, form, cc=c_sq_norms)


def swap_deltas(
    D: Array,
    d1: Array,
    d2: Array,
    n1: Array,
    valid: Array,
    *,
    k: int,
    bg: Optional[int] = None,
    force_pallas: Optional[bool] = None,
    config: Optional[KernelConfig] = None,
) -> Array:
    """FasterPAM swap-sweep ΔTD matrix ``[k, g]`` (the MSA build hot path).

    ``D``: [g, g] group dissimilarities; ``d1``/``d2``: [g] nearest /
    second-nearest medoid distances; ``n1``: [g] int32 nearest-medoid slot;
    ``valid``: [g] point mask. Returns the *unmasked* swap deltas
    ``dTD[i, j] = S[j] + T[i, j]`` — callers mask medoid / invalid columns
    before taking argmins (``core.kmedoids``).

    On the Pallas path the [g, g] gain / removal intermediates are streamed
    in ``[bg, g]`` row tiles and only the [k, g] accumulator persists; the
    CPU path runs the pure-jnp oracle (``ref.swap_deltas_ref``).
    """
    fp = _fp(force_pallas, config)
    if _on_tpu() or fp:
        knobs = resolve_blocks(
            "swap", "none", str(D.dtype), (D.shape[0],), config, bg=bg,
        )
        return _kmk.swap_deltas_pallas(
            D, d1, d2, n1, valid, k=k, interpret=not _on_tpu(), **knobs
        )
    return _ref.swap_deltas_ref(D, d1, d2, n1, valid, k)


def scan_quantized(
    Q: Array,
    codes: Array,
    scales: Array,
    cand_idx: Array,
    cand_ok: Array,
    distance="l2",
    *,
    k: int,
    block: int,
    slot_valid: Optional[Array] = None,
    code_format: str = "dense",
    bq: Optional[int] = None,
    bn: Optional[int] = None,
    force_pallas: Optional[bool] = None,
    config: Optional[KernelConfig] = None,
) -> tuple[Array, Array]:
    """Stage-1 two-stage search: rank per-query candidates against the
    *quantised* payload tier in its native dtype (DESIGN.md §3.6).

    ``Q``: [b, d] queries; ``codes``: [n, dc] quantised leaf payload — int8
    symmetric / fp16 (``code_format="dense"``, ``dc == d``), two int4
    nibbles per byte (``"int4"``, ``dc = ceil(d/2)``) or packed sign bits
    (``"binary"``, ``dc = ceil(d/8)``); ``scales``: [nb] per-block
    dequantisation scales, ``block`` rows per block; ``cand_idx``/``cand_ok``:
    [b, w] candidate rows into ``codes`` + validity (the NSA beam layout).
    Returns (dists[b, k] ascending, slots[b, k] into the candidate axis) —
    *approximate* distances (quantisation error ~ scale/2 per coordinate for
    int8, ~scale/2 at 3 bits for int4, sign-only for binary); callers rerank
    the survivors against the exact fp32 payload.

    The gather stays in the packed container dtype — 1 byte/element for
    int8, 0.5 (int4) or 0.125 (binary) bytes per *dimension* — and every
    dispatch path unpacks + dequantises per-tile in VMEM / in-register
    (``kernels/quantized.py``; ``ref.unpack_codes`` on the jnp paths).

    ``slot_valid``: optional bool[n] tombstone mask over the code table
    (True = live row). Folded into ``cand_ok`` *before* the scan
    (``ref.fold_slot_valid``), so deleted rows rank as ``BIG`` on every
    dispatch path without the codes being rewritten.
    """
    fp = _fp(force_pallas, config)
    cand_ok = _ref.fold_slot_valid(cand_idx, cand_ok, slot_valid)
    nb = scales.shape[0]
    C = jnp.take(codes, cand_idx, axis=0)  # [b, w, dc] packed container
    srows = jnp.take(scales, jnp.clip(cand_idx // block, 0, nb - 1))  # [b, w]
    d = Q.shape[-1]
    form = resolve_form(distance)
    if form is None:
        from repro.core import distances as dist_lib

        dist = dist_lib.get(distance)
        Cu = _ref.unpack_codes(C, code_format, d)
        Cf = Cu.astype(jnp.float32) * srows.astype(jnp.float32)[..., None]
        D = dist.point(Q[:, None, :], Cf)
        D = jnp.where(cand_ok, D, dist_lib.BIG)
        neg, slots = jax.lax.top_k(-D, k)
        return -neg, slots.astype(jnp.int32)
    if _on_tpu() or fp:
        dtype_key = code_format if code_format != "dense" else str(codes.dtype)
        knobs = resolve_blocks(
            "scan", form, dtype_key, (Q.shape[0], cand_idx.shape[1], d),
            config, bq=bq, bn=bn,
        )
        return _qk.scan_pallas(
            Q, C, srows, cand_ok,
            form=form, k=k, fmt=code_format, interpret=not _on_tpu(), **knobs,
        )
    return _ref.scan_quantized_ref(Q, C, srows, cand_ok, k, form,
                                   fmt=code_format)


def rank_gathered(
    Q: Array,
    points: Array,
    sq_norms: Array,
    cand_idx: Array,
    cand_ok: Array,
    distance="l2",
    *,
    k: int,
    slot_valid: Optional[Array] = None,
    run_starts: Optional[Array] = None,
    bq: Optional[int] = None,
    bn: Optional[int] = None,
    force_pallas: Optional[bool] = None,
    config: Optional[KernelConfig] = None,
) -> tuple[Array, Array]:
    """Rank per-query candidates given as *indices* into a shared point table
    (the NSA beam-search layout: ``cand_idx[b]`` indexes rows of ``points``).

    Returns (dists[b, k] ascending, slots[b, k] into the candidate axis).

    ``slot_valid``: optional bool[n] tombstone mask over the point table
    (True = live). Folded into ``cand_ok`` before dispatch
    (``ref.fold_slot_valid``) — deleted rows rank as ``BIG`` on every path
    (gemm+gather, gathered cube, Pallas) without touching ``points``.

    ``run_starts``: optional int32 [b, r] when the candidates are child
    runs, as the beam descent makes them: ``cand_idx[:, j * mc + t] ==
    clip(run_starts[:, j] + t, 0, n - 1)`` with ``mc = w // r``.

    Dispatch picks the cheapest way to avoid the [b, w, d] gathered cube:

    * TPU / force_pallas, child runs of a table stored column-major
      (:func:`column_major`) — ``rank_runs_pallas``: the runs' lane blocks
      are fetched from the table where it lies and ranked in the same
      kernel. A row gather would make XLA relayout the whole table first,
      on every call.
    * TPU / force_pallas — row gather + the fused ``rank_pallas`` kernel
      (candidate blocks stream through VMEM; the [b, w] distance matrix
      never reaches HBM).
    * CPU, Gram form, w a sizeable fraction of the table — one
      ``pairwise_ref`` cross matrix (a gemm; arithmetic identical to the
      dense path, which keeps full-width beam bit-compatible with
      ``search_dense``) followed by a scalar gather of the candidate
      columns. No [b, w, d] cube, and gemm beats gather-then-reduce by a
      wide margin on CPU.
    * CPU, small w or non-Gram form — gather the rows and rank the cube
      (cache-resident at these sizes; broadcast forms have no gemm).
    """
    fp = _fp(force_pallas, config)
    cand_ok = _ref.fold_slot_valid(cand_idx, cand_ok, slot_valid)
    b, w = cand_idx.shape
    n = points.shape[0]
    form = resolve_form(distance)
    if (
        form in _ref.GRAM_FORMS
        and not (_on_tpu() or fp)
        and n <= 24 * w
    ):
        D = _ref.pairwise_ref(Q, points, form)  # [b, n] — one gemm + epilogue
        d = jnp.take_along_axis(D, cand_idx, axis=1)  # [b, w]
        d = jnp.where(cand_ok, d, _ref.BIG)
        neg, slots = jax.lax.top_k(-d, k)
        return -neg, slots.astype(jnp.int32)
    cc = (
        jnp.take(sq_norms, cand_idx)
        if form in _ref.NORM_FORMS and sq_norms is not None
        else None
    )
    if (
        run_starts is not None
        and form is not None
        and (_on_tpu() or fp)
        and points.dtype == jnp.float32
        and w // run_starts.shape[1] <= _tiling.LANE
        and _tiling.vmem_rank_runs(points.shape[1]) <= _tiling.VMEM_BUDGET
        and (cc is not None or form not in _ref.NORM_FORMS)
        and column_major(points)
    ):
        return _tk.rank_runs_pallas(
            Q, points.T, run_starts, cand_ok, cc,
            form=form, k=k, interpret=not _on_tpu(),
        )
    C = jnp.take(points, cand_idx, axis=0)  # [b, w, d]
    return rank_candidates(
        Q, C, cand_ok, distance, k=k, c_sq_norms=cc,
        bq=bq, bn=bn, force_pallas=fp, config=config,
    )
