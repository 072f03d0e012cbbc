"""Fused distance + streaming top-k Pallas kernel ("flash k-NN").

NSA's leaf ranking and the brute-force baseline both do ``distances -> top_k``.
Materialising the full ``[q, n]`` matrix in HBM first makes the op memory-bound
(bytes ~ 4qn); this kernel streams database blocks through VMEM, keeping only a
running ``[bq, k]`` top-k state per query tile — the same trick flash-attention
uses for the softmax, applied to k-selection:

  grid = (q/bq, n/bn)        # db axis sequential ("arbitrary")
  state: o_dists[bq, k], o_ids[bq, k] live in the *output* refs, revisited
  per step:   d = dist(q_tile, db_tile)          # MXU (gram) or VPU form
              state = merge_topk(state, d)       # k rounds of extract-min

HBM traffic drops from ``4qn`` bytes (write + read the matrix, then select) to
``~(q + n) d`` input bytes + ``8qk`` output bytes — for the recsys
``retrieval_cand`` cell (1 query x 1M candidates) that's the difference
between memory-bound and compute-bound (see EXPERIMENTS.md §Perf).

The merge (:func:`merge_topk`, shared by every fused top-k kernel) keeps the
``[bq, k]`` state and the ``[bq, bn]`` tile as two pieces and runs ``k`` rounds
of extract-min over both: lane ``min``, first index of that min by ``iota`` +
``where`` + ``min``, then the taken slot is masked to ``inf``. Mosaic lowers
every one of those ops; it lowers neither ``top_k`` nor a per-row lane
gather, nor a lane-unaligned ``concatenate``. Ties go to the
state first and then to the lower tile column — the order ``top_k`` gives
over ``concat([state, tile])``. Padded database rows are masked to ``BIG`` via
their global column index, so callers may pad freely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.ref import BIG, FORMS, GRAM_FORMS, NORM_FORMS, PRECISION

Array = jax.Array

_EPS = 1e-12


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_distance(form: str, q: Array, db: Array) -> Array:
    """[bq, d] x [bn, d] -> [bq, bn] distance tile (full-d blocks)."""
    q = q.astype(jnp.float32)
    db = db.astype(jnp.float32)
    if form in GRAM_FORMS:
        g = jnp.dot(q, db.T, preferred_element_type=jnp.float32,
                    precision=PRECISION)
        if form == "dot":
            return -g
        qq = jnp.sum(q * q, axis=1, keepdims=True)
        dd = jnp.sum(db * db, axis=1, keepdims=True)
        if form in ("sqeuclidean", "l2"):
            d2 = jnp.maximum(qq + dd.T - 2.0 * g, 0.0)
            return d2 if form == "sqeuclidean" else jnp.sqrt(d2)
        norm = jnp.sqrt(jnp.maximum(qq, _EPS)) * jnp.sqrt(jnp.maximum(dd.T, _EPS))
        return 1.0 - jnp.clip(g / norm, -1.0, 1.0)
    diff = jnp.abs(q[:, None, :] - db[None, :, :])
    if form == "l1":
        return jnp.sum(diff, axis=-1)
    if form == "chebyshev":
        return jnp.max(diff, axis=-1)
    raise ValueError(form)


def merge_topk(best_d: Array, best_i: Array, tile_d: Array, base) -> tuple:
    """Merge a ``[bq, bn]`` distance tile into a running ``[bq, k]`` top-k.

    ``best_d`` / ``best_i``: the running state, ascending; ``tile_d``: the
    new tile, whose column ``c`` carries id ``base + c``. Returns the new
    ``(dists, ids)`` state: the ``k`` smallest of state and tile, ascending,
    ties to the state and then to the lower column (``top_k``'s order
    over ``concat([state, tile])``). Built from lane reductions, ``iota``
    and ``where`` only, so it lowers on Mosaic inside a kernel body.
    """
    bq, k = best_d.shape
    bn = tile_d.shape[1]
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    lane_n = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)

    def take_one(r, carry):
        sd, td, od, oi = carry
        ms = jnp.min(sd, axis=1, keepdims=True)  # [bq, 1]
        mt = jnp.min(td, axis=1, keepdims=True)
        from_state = ms <= mt
        ps = jnp.min(jnp.where(sd == ms, lane_k, k), axis=1, keepdims=True)
        pt = jnp.min(jnp.where(td == mt, lane_n, bn), axis=1, keepdims=True)
        hit_s = lane_k == ps
        id_s = jnp.sum(jnp.where(hit_s, best_i, 0), axis=1, keepdims=True)
        sd = jnp.where(hit_s & from_state, jnp.inf, sd)
        td = jnp.where((lane_n == pt) & ~from_state, jnp.inf, td)
        out = lane_k == r
        od = jnp.where(out, jnp.minimum(ms, mt), od)
        oi = jnp.where(out, jnp.where(from_state, id_s, base + pt), oi)
        return sd, td, od, oi

    _, _, od, oi = jax.lax.fori_loop(
        0, k, take_one, (best_d, tile_d, best_d, best_i)
    )
    return od, oi


def _knn_kernel(q_ref, db_ref, od_ref, oi_ref, *, form, k, bn, n_valid):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full_like(od_ref, BIG)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    d = _tile_distance(form, q_ref[...], db_ref[...])  # [bq, bn]
    bq = d.shape[0]
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    d = jnp.where(col < n_valid, d, BIG)

    od_ref[...], oi_ref[...] = merge_topk(od_ref[...], oi_ref[...], d, j * bn)


@functools.partial(
    jax.jit, static_argnames=("form", "k", "bq", "bn", "interpret")
)
def knn_pallas(
    Q: Array,
    DB: Array,
    *,
    form: str,
    k: int,
    bq: int = 128,
    bn: int = 512,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused brute-force k-NN: returns (dists[q, k] ascending, ids[q, k]).

    Blocks carry full ``d`` (no d-chunking) — ANN feature dims are small
    (<= a few K), so ``[bq, d] + [bn, d]`` comfortably fits VMEM.
    """
    if form not in FORMS:
        raise ValueError(f"unsupported form {form!r}")
    nq, d = Q.shape
    n, d2 = DB.shape
    if d != d2:
        raise ValueError(f"dim mismatch {d} vs {d2}")
    if k > n:
        raise ValueError(f"k={k} > n={n}")

    # Backend-real tiling: shrink blocks overhanging the (padded) problem,
    # bound the per-step VMEM footprint by halving the database tile.
    bq = tiling.shrink(bq, nq, tiling.sublane(Q.dtype))
    bn = tiling.shrink(bn, n, tiling.LANE)
    bn = tiling.fit_budget(
        bn,
        lambda x: tiling.vmem_knn(bq, x, d, k, DB.dtype.itemsize),
        floor=min(bn, tiling.LANE),
    )

    qp, np_ = _ceil_to(nq, bq), _ceil_to(n, bn)
    Qp = jnp.pad(Q, ((0, qp - nq), (0, 0)))
    DBp = jnp.pad(DB, ((0, np_ - n), (0, 0)))
    grid = (qp // bq, np_ // bn)

    kernel = functools.partial(
        _knn_kernel, form=form, k=k, bn=bn, n_valid=n
    )
    dists, ids = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
        ],
        interpret=interpret,
        name="knn_pallas",
    )(Qp, DBp)
    return dists[:nq], ids[:nq]


# ---------------------------------------------------------------------------
# Fused gather -> distance -> top-k leaf ranking (batched beam search)
# ---------------------------------------------------------------------------


def _rank_tile_distance(form: str, qs, cs, cc=None) -> Array:
    """Per-query distance tile ``[bq, bn]`` from matching feature planes.

    ``qs``: ``[bq, dp]`` query planes; ``cs``: the matching ``[bq, bn, dp]``
    candidate planes. Dense rows are one plane holding all of ``d``; packed
    codes (``quantized.py``) unpack into several planes that partition the
    dimensions, so no interleaving reshape ever runs in VMEM. Every query
    row sees its *own* candidate rows (the beam-search layout), so there is
    no shared [bq, d] x [d, bn] matmul form; the reduction over ``d`` runs
    on the VPU against the VMEM-resident candidate block, mirroring
    ``pairwise._vpu_kernel``. Norm-consuming forms take the gathered
    ``||c||^2`` tile (``cc``) from the index-side cache when given, and
    reduce it from the planes otherwise.
    """
    # Per-plane partial terms, combined across planes: Gram forms carry
    # (q.c, |q|^2, |c|^2), the VPU forms their one reduction.
    combine = jnp.maximum if form == "chebyshev" else jnp.add
    acc = None
    for q, c in zip(qs, cs):
        q = q.astype(jnp.float32)
        c = c.astype(jnp.float32)
        if form in GRAM_FORMS:
            part = (
                jnp.sum(q[:, None, :] * c, axis=-1),  # [bq, bn]
                jnp.sum(q * q, axis=-1)[:, None],
                jnp.sum(c * c, axis=-1)
                if cc is None and form in NORM_FORMS else 0.0,
            )
        elif form in ("l1", "chebyshev"):
            diff = jnp.abs(q[:, None, :] - c)
            part = (jnp.sum(diff, axis=-1) if form == "l1"
                    else jnp.max(diff, axis=-1),)
        else:
            raise ValueError(form)
        acc = part if acc is None else tuple(map(combine, acc, part))
    if form not in GRAM_FORMS:
        return acc[0]
    g, qq, cc_planes = acc
    return _gram_distance(form, g, qq, cc_planes if cc is None else cc)


def _gram_distance(form: str, g, qq, cc) -> Array:
    """A Gram form's distance from the dot products ``g`` [bq, bn], the
    query norms ``qq`` [bq, 1] and the candidate norms ``cc`` [bq, bn]."""
    if form == "dot":
        return -g
    cc = cc.astype(jnp.float32)
    if form in ("sqeuclidean", "l2"):
        d2 = jnp.maximum(qq + cc - 2.0 * g, 0.0)
        return d2 if form == "sqeuclidean" else jnp.sqrt(d2)
    norm = jnp.sqrt(jnp.maximum(qq, _EPS)) * jnp.sqrt(jnp.maximum(cc, _EPS))
    return 1.0 - jnp.clip(g / norm, -1.0, 1.0)


def _rank_kernel(q_ref, c_ref, ok_ref, *rest, form, k, bn):
    # rest is (cc_ref, od_ref, oi_ref) for norm-consuming forms (l2 /
    # sqeuclidean / cosine stream the gathered norm tile) and (od_ref,
    # oi_ref) otherwise.
    if form in NORM_FORMS:
        cc_ref, od_ref, oi_ref = rest
    else:
        cc_ref, (od_ref, oi_ref) = None, rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full_like(od_ref, BIG)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    cc = cc_ref[...] if cc_ref is not None else None
    d = _rank_tile_distance(form, [q_ref[...]], [c_ref[...]], cc)
    # widen the int8 mask first: Mosaic cannot relayout an int8 compare
    # onto the reduced distance tile ("Lane broadcast")
    d = jnp.where(ok_ref[...].astype(jnp.int32) != 0, d, BIG)
    od_ref[...], oi_ref[...] = merge_topk(od_ref[...], oi_ref[...], d, j * bn)


@functools.partial(
    jax.jit, static_argnames=("form", "k", "bq", "bn", "interpret")
)
def rank_pallas(
    Q: Array,
    C: Array,
    ok: Array,
    cc: Array = None,
    *,
    form: str,
    k: int,
    bq: int = 8,
    bn: int = 256,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused masked candidate ranking: the NSA leaf/beam hot path.

    ``Q``: [b, d] queries; ``C``: [b, w, d] per-query gathered candidates;
    ``ok``: [b, w] validity mask; ``cc``: optional gathered squared candidate
    norms [b, w] (l2 / sqeuclidean / cosine; reduced from ``C`` if absent).
    Returns (dists[b, k] ascending, slots[b, k] into the ``w`` axis; masked
    slots rank as ``BIG``).

    The [b, w] distance matrix is never materialised in HBM: candidate
    blocks of [bq, bn, d] stream through VMEM and only the running [bq, k]
    top-k state persists, exactly like :func:`knn_pallas` but with a
    per-query candidate axis.
    """
    if form not in FORMS:
        raise ValueError(f"unsupported form {form!r}")
    b, d = Q.shape
    b2, w, d2 = C.shape
    if b != b2 or d != d2:
        raise ValueError(f"shape mismatch {Q.shape} vs {C.shape}")
    if k > w:
        raise ValueError(f"k={k} > candidate width w={w}")

    # Backend-real tiling: the [bq, bn, d] candidate cube dominates VMEM —
    # shrink overhanging blocks, then halve bn until the cube fits.
    bq = tiling.shrink(bq, b, tiling.sublane(Q.dtype))
    bn = tiling.shrink(bn, w, tiling.LANE)
    bn = tiling.fit_budget(
        bn,
        lambda x: tiling.vmem_rank(bq, x, d, k, C.dtype.itemsize),
        floor=min(bn, tiling.LANE),
    )

    bp, wp = _ceil_to(b, bq), _ceil_to(w, bn)
    Qp = jnp.pad(Q, ((0, bp - b), (0, 0)))
    Cp = jnp.pad(C, ((0, bp - b), (0, wp - w), (0, 0)))
    okp = jnp.pad(ok.astype(jnp.int8), ((0, bp - b), (0, wp - w)))
    grid = (bp // bq, wp // bn)

    in_arrays = [Qp, Cp, okp]
    in_specs = [
        pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        pl.BlockSpec((bq, bn, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
    ]
    if form in NORM_FORMS:
        if cc is None:
            cc = jnp.sum(C.astype(jnp.float32) * C, axis=-1)
        ccp = jnp.pad(cc.astype(jnp.float32), ((0, bp - b), (0, wp - w)))
        in_arrays.append(ccp)
        in_specs.append(pl.BlockSpec((bq, bn), lambda i, j: (i, j)))

    kernel = functools.partial(_rank_kernel, form=form, k=k, bn=bn)
    dists, slots = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ],
        interpret=interpret,
        name="rank_pallas",
    )(*in_arrays)
    # Honour the slot contract (in [0, w)) even for masked/short rows: the
    # -1 init and padded columns rank as BIG but must not leak out-of-range
    # indices to host-side consumers (a NumPy per-row gather would wrap them).
    return dists[:b], jnp.clip(slots[:b], 0, w - 1)


# ---------------------------------------------------------------------------
# Child runs ranked where a column-major table lies (the beam descent)
# ---------------------------------------------------------------------------


def _rank_runs_kernel(s_ref, q_ref, qb_ref, *refs, form, k, g, mc, r):
    # refs: a (first, second) lane block of the table per query of the
    # group, ok [g, wp], cc [g, wp] (norm forms), the two outputs, and the
    # [wp / 128 + 1, g, 128] scratch the runs' partial distances gather in.
    blocks, rest = refs[:2 * g], refs[2 * g:]
    if form in NORM_FORMS:
        ok_ref, cc_ref, od_ref, oi_ref, acc_ref = rest
    else:
        (ok_ref, od_ref, oi_ref, acc_ref), cc_ref = rest, None
    i, j = pl.program_id(0), pl.program_id(1)
    lane = tiling.LANE
    col = j * mc  # the run's first slot on the candidate axis
    dest, v = col % lane, col // lane
    row = jax.lax.broadcasted_iota(jnp.int32, (g, 2 * lane), 0)
    parts = jnp.zeros((g, 2 * lane), jnp.float32)
    for q in range(g):
        # [d, 256] lanes of the run's two blocks against the query as a
        # column: the feature axis reduces over sublanes.
        two = jnp.concatenate([blocks[2 * q][...], blocks[2 * q + 1][...]],
                              axis=1).astype(jnp.float32)
        qc = qb_ref[q].astype(jnp.float32)
        qc = jnp.concatenate([qc, qc], axis=1)
        if form in GRAM_FORMS:
            part = jnp.sum(two * qc, axis=0, keepdims=True)
        elif form == "l1":
            part = jnp.sum(jnp.abs(two - qc), axis=0, keepdims=True)
        else:
            part = jnp.max(jnp.abs(two - qc), axis=0, keepdims=True)
        # Rotate the run's first lane to its slot's lane.
        s = s_ref[i * g + q, j]
        part = pltpu.roll(part, (dest - s % lane + 2 * lane) % (2 * lane), 1)
        parts = jnp.where(row == q, part, parts)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (g, 2 * lane), 1)
    put = (lanes >= dest) & (lanes < dest + mc)
    acc_ref[v] = jnp.where(put[:, :lane], parts[:, :lane], acc_ref[v])
    acc_ref[v + 1] = jnp.where(put[:, lane:], parts[:, lane:], acc_ref[v + 1])

    @pl.when(j == r - 1)
    def _rank():
        part = jnp.concatenate(
            [acc_ref[c] for c in range(acc_ref.shape[0] - 1)], axis=1)
        if form in GRAM_FORMS:
            qv = q_ref[...].astype(jnp.float32)
            qq = jnp.sum(qv * qv, axis=-1)[:, None]
            cc = cc_ref[...] if cc_ref is not None else None
            part = _gram_distance(form, part, qq, cc)
        d = jnp.where(ok_ref[...].astype(jnp.int32) != 0, part, BIG)
        od_ref[...], oi_ref[...] = merge_topk(
            jnp.full(od_ref.shape, BIG, jnp.float32),
            jnp.full(oi_ref.shape, -1, jnp.int32), d, 0)


@functools.partial(jax.jit, static_argnames=("form", "k", "interpret"))
def rank_runs_pallas(
    Q: Array,
    PT: Array,
    starts: Array,
    ok: Array,
    cc: Array = None,
    *,
    form: str,
    k: int,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """:func:`rank_pallas` for child runs of a table stored column-major,
    ranked where the table lies.

    ``PT``: [d, n], the transposed view of the table: the stored bytes as
    they lie, which XLA passes to the kernel as a bitcast. ``starts``:
    [b, r] first row of each run; the candidate axis is the runs laid end
    to end, ``w = r * mc`` with ``mc = ok.shape[1] // r``: slot
    ``j * mc + t`` is row ``starts[:, j] + t``. ``ok`` / ``cc``: [b, w] as
    in :func:`rank_pallas` (``cc`` is required for the norm forms).
    Returns (dists[b, k] ascending, slots[b, k] into the ``w`` axis).

    Grid ``(b / 8, r)``: each step the Pallas pipeline fetches, for each of
    8 queries, the one or two [d, 128] lane blocks that hold its run
    ``j`` (block indices from the scalar-prefetched starts). The kernel
    reduces them against the query over the feature (sublane) axis,
    rotates the run's ``mc`` lanes to their slots and keeps them in VMEM;
    after the last run it ranks the [8, w] row with :func:`merge_topk`.
    A row gather would make XLA relayout the whole table first; no [b, w,
    d] cube reaches HBM either. Dists match :func:`rank_pallas` to float32
    rounding: the feature sum runs in another order.
    """
    if form not in FORMS:
        raise ValueError(f"unsupported form {form!r}")
    b, d = Q.shape
    d2, n = PT.shape
    b2, r = starts.shape
    w = ok.shape[1]
    mc = w // r
    if b != b2 or d != d2 or ok.shape[0] != b or w != r * mc:
        raise ValueError(f"shape mismatch {Q.shape} / {PT.shape} / "
                         f"{starts.shape} / {ok.shape}")
    if not 0 < mc <= tiling.LANE:
        raise ValueError(f"run length {mc} outside (0, {tiling.LANE}]")
    if k > w:
        raise ValueError(f"k={k} > candidate width w={w}")
    if form in NORM_FORMS and cc is None:
        raise ValueError(f"form {form!r} needs the candidate norms cc")

    lane = tiling.LANE
    g = tiling.RUNS_GROUP
    bp, wp = _ceil_to(b, g), _ceil_to(w, lane)
    nb = pl.cdiv(n, lane)
    s0 = jnp.pad(jnp.clip(starts, 0, n - 1).astype(jnp.int32),
                 ((0, bp - b), (0, 0)))
    Qp = jnp.pad(Q, ((0, bp - b), (0, 0)))
    in_arrays = [Qp, jnp.broadcast_to(Qp[:, :, None], (bp, d, lane))]
    in_specs = [pl.BlockSpec((g, d), lambda i, j, s: (i, 0)),
                pl.BlockSpec((g, d, lane), lambda i, j, s: (i, 0, 0))]
    for q in range(g):
        first = functools.partial(
            lambda q, i, j, s: (0, s[i * g + q, j] // lane), q)
        second = functools.partial(
            lambda q, i, j, s: (0, jnp.minimum(s[i * g + q, j] // lane + 1,
                                               nb - 1)), q)
        in_arrays += [PT, PT]
        in_specs += [pl.BlockSpec((d, lane), first),
                     pl.BlockSpec((d, lane), second)]
    in_arrays.append(jnp.pad(ok.astype(jnp.int8), ((0, bp - b), (0, wp - w))))
    in_specs.append(pl.BlockSpec((g, wp), lambda i, j, s: (i, 0)))
    if form in NORM_FORMS:
        in_arrays.append(jnp.pad(cc.astype(jnp.float32),
                                 ((0, bp - b), (0, wp - w))))
        in_specs.append(pl.BlockSpec((g, wp), lambda i, j, s: (i, 0)))

    kernel = functools.partial(_rank_runs_kernel, form=form, k=k, g=g, mc=mc,
                               r=r)
    dists, slots = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp // g, r),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((g, k), lambda i, j, s: (i, 0)),
                       pl.BlockSpec((g, k), lambda i, j, s: (i, 0))],
            scratch_shapes=[pltpu.VMEM((wp // lane + 1, g, lane),
                                       jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ],
        interpret=interpret,
        name="rank_pallas",
    )(s0, *in_arrays)
    return dists[:b], jnp.clip(slots[:b], 0, w - 1)
