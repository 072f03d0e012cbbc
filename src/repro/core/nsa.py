"""NSA — Neighbours Search Algorithm (paper Algorithm 2), in JAX.

Two execution modes over the same :class:`~repro.core.msa.PDASCIndexData`,
both dispatching every distance evaluation and ranking step through the
kernel layer (``repro.kernels.ops`` — DESIGN.md §3.3):

``search_dense``
    Faithful masked translation of Algorithm 2. The per-level candidate set
    becomes a boolean mask over the whole level:

        active[L]   = valid & (d(q, p) < r)                    (top level)
        active[l]   = active[l+1][parent] & valid & (d < r)    (inner levels)
        candidates  = active[1][parent_0] & valid              (leaf level)

    Note the leaf level is *not* radius-filtered by default — Algorithm 2
    returns ``levelPoints[0][idCandidates]`` without re-checking ``r``
    (``leaf_radius_filter`` exposes the stricter variant). Finally candidates
    are ranked by distance and the k nearest returned. Semantically identical
    to the paper's recursion (tests check this against a literal Python port),
    but every leaf distance is *computed* then masked — the TPU-idiomatic
    form, used for validation and small indexes. Per level it costs one
    ``ops.pairwise_distance`` call (MXU Gram matmul / tiled VPU kernel on
    TPU; streamed reference on CPU) — never an ``[B, n, d]`` broadcast cube.

``search_beam``
    The TPU-native pruned search (DESIGN.md §3.2), *batched over the query
    axis*: per level the whole batch performs one ``[B, W]`` candidate gather
    and one fused ``ops.rank_candidates`` call (gather -> distance -> top-k
    streamed through VMEM), which yields the per-query beam directly; only
    the sibling-contiguous child blocks of the beam survive to the next
    level — static shapes, real FLOP pruning, no per-query vmap.
    ``beam >= level size`` at every level reproduces ``search_dense``
    results exactly (the candidate set is then complete, and the rowwise
    kernel arithmetic matches the pairwise kernel element-for-element).

Both are jit-friendly over a query batch. Results are ``(dists[k], ids[k])``
sorted ascending; empty slots hold ``BIG`` / -1.

``search_beam_vmap`` preserves the pre-kernel-layer per-query scalar search
(a ``vmap`` of ``dist.point`` gathers). It exists as the benchmark baseline
for the batched path (``benchmarks/bench_search.py --mode beam``) and as an
independent semantic oracle in the tests.

``descend_beam`` exposes the beam descent (levels L..1) without the leaf
ranking — stage 0 of the tiered-store two-stage search
(``repro.store.two_stage``, DESIGN.md §3.6), which replaces the fused fp32
leaf rank with a quantised payload scan + exact out-of-core rerank.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import distances as dist_lib
from repro.core.distances import BIG
from repro.core.msa import PDASCIndexData
from repro.kernels import ops as kops
from repro.kernels import ref as kref

Array = jax.Array


class SearchResult(NamedTuple):
    dists: Array  # f32[..., k] ascending; BIG for missing
    ids: Array  # int32[..., k] original dataset rows; -1 for missing
    n_candidates: Array  # int32[...] leaf candidates examined (pruning metric)


def _per_level_radii(r, n_levels: int) -> tuple:
    """Broadcast a scalar radius to per-level radii, indexed by level —
    ``radii[0]`` applies at the leaf, ``radii[-1]`` at the top. A sequence
    enables the paper's future-work dynamic radius."""
    if isinstance(r, (list, tuple)):
        if len(r) != n_levels:
            raise ValueError(f"need {n_levels} radii, got {len(r)}")
        return tuple(r)
    return tuple([r] * n_levels)


# ---------------------------------------------------------------------------
# Dense-masked (faithful) mode
# ---------------------------------------------------------------------------


def _search_dense_batch(
    index: PDASCIndexData,
    dist: dist_lib.Distance,
    Q: Array,  # [B, d]
    k: int,
    radii: tuple,
    leaf_radius_filter: bool,
    kernel: kops.KernelConfig,
    with_stats: bool = True,
    slot_valid: Optional[Array] = None,
) -> SearchResult:
    """Batched masked NSA: per level one [B, n_l] distance matrix.

    Every level is one ``ops.pairwise_distance`` dispatch: Gram-form
    distances (l2/cosine/dot) become a single MXU matmul per level, the
    broadcast forms stream ``row_chunk`` column slabs — never the [B, n, d]
    broadcast cube (the Pallas ``pairwise`` kernel implements the identical
    tiling on real TPU).
    """
    levels = index.levels
    L = len(levels) - 1

    def pw(pts):
        return kops.pairwise_distance(Q, pts, dist, config=kernel)

    top = levels[L]
    D = pw(top.points)  # [B, n_L]
    active = top.valid[None, :] & (D < radii[L])

    for l in range(L - 1, 0, -1):
        lv = levels[l]
        D = pw(lv.points)
        up_n = levels[l + 1].points.shape[0]
        parent_ok = jnp.where(
            (lv.parent >= 0)[None, :],
            jnp.take(active, jnp.clip(lv.parent, 0, up_n - 1), axis=1),
            False,
        )
        active = parent_ok & lv.valid[None, :] & (D < radii[l])

    leaf = levels[0]
    D = pw(leaf.points)  # [B, n_0]
    up_n = levels[1].points.shape[0] if L >= 1 else 1
    if L >= 1:
        parent_ok = jnp.where(
            (leaf.parent >= 0)[None, :],
            jnp.take(active, jnp.clip(leaf.parent, 0, up_n - 1), axis=1),
            False,
        )
        cand = parent_ok & leaf.valid[None, :]
    else:
        cand = jnp.broadcast_to(leaf.valid[None, :], D.shape)
    if slot_valid is not None:  # tombstone mask: deleted leaf slots drop out
        cand = cand & slot_valid[None, :]
    if leaf_radius_filter:
        cand = cand & (D < radii[0])

    d_masked = jnp.where(cand, D, BIG)
    dists, slots = jax.lax.top_k(-d_masked, k)
    dists = -dists
    ids = jnp.where(dists < BIG / 2, jnp.take(index.leaf_ids, slots), -1)
    n_cand = (jnp.sum(cand, axis=1, dtype=jnp.int32) if with_stats
              else jnp.zeros((D.shape[0],), jnp.int32))
    return SearchResult(dists=dists, ids=ids, n_candidates=n_cand)


@functools.partial(
    jax.jit,
    static_argnames=(
        "dist", "k", "r", "leaf_radius_filter", "with_stats", "kernel",
    ),
)
def search_dense(
    index: PDASCIndexData,
    Q: Array,
    *,
    dist: dist_lib.Distance,
    k: int = 10,
    r,
    leaf_radius_filter: bool = False,
    with_stats: bool = True,
    kernel: Optional[kops.KernelConfig] = None,
    slot_valid: Optional[Array] = None,
) -> SearchResult:
    """Batched faithful NSA. ``Q``: [B, d] (or [d]).

    ``with_stats=False`` skips the candidate-count reduction (one full
    [B, n] pass) — the serving configuration. ``kernel`` carries the
    kernel-layer block knobs (None = defaults). ``slot_valid`` is the online
    substrate's tombstone mask over leaf slots (True = live, DESIGN.md
    §3.7): deleted slots never become candidates; the navigation levels are
    untouched (prototypes are copies, not results).
    """
    radii = _per_level_radii(r, len(index.levels))
    squeeze = Q.ndim == 1
    Qb = Q[None, :] if squeeze else Q
    res = _search_dense_batch(
        index, dist, Qb, k=k, radii=radii,
        leaf_radius_filter=leaf_radius_filter,
        kernel=kernel or kops.DEFAULT, with_stats=with_stats,
        slot_valid=slot_valid,
    )
    if squeeze:
        res = jax.tree.map(lambda a: a[0], res)
    return res


# ---------------------------------------------------------------------------
# Batched beam mode (the kernel-layer hot path)
# ---------------------------------------------------------------------------


def _descend_beam(
    index: PDASCIndexData,
    dist: dist_lib.Distance,
    Q: Array,  # [B, d]
    radii: tuple,
    beams: tuple,
    max_children: tuple,
    kernel: kops.KernelConfig,
) -> tuple[Array, Array, Array]:
    """Levels L..1 of the batched beam search: per level one gather + one
    fused rank. Returns the leaf candidate table ``(cand_idx [B, W],
    cand_ok [B, W])`` — the input of the leaf ranking stage, whichever
    payload tier performs it (the fused fp32 rank of :func:`search_beam`, or
    the quantised scan -> exact rerank of ``repro.store.two_stage``) — and
    the child runs it is made of, ``starts [B, beam]``: candidate
    ``j * mc + t`` is row ``starts[:, j] + t``. Each level's rank is given
    its runs, so a column-major table is ranked where it lies
    (``ops.rank_gathered``).

    The radius filter is applied *after* the beam selection: candidates
    sort ascending by distance, so every in-radius candidate outranks every
    out-of-radius one and post-filtering selects the identical beam — but
    the select itself stays one fused kernel call. Requires a multi-level
    index (callers special-case L == 0, where every valid leaf slot is a
    candidate).
    """
    levels = index.levels
    L = len(levels) - 1
    B = Q.shape[0]

    # Every top-level prototype is a candidate for every query, so the top
    # ranking is one cross pairwise_distance call (no per-query gather —
    # replicating the level B times would cost [B, n_top, d] for what is a
    # shared candidate set) followed by one top-k.
    top = levels[L]
    n_top = top.points.shape[0]
    D_top = kops.pairwise_distance(Q, top.points, dist, config=kernel)
    D_top = jnp.where(top.valid[None, :], D_top, BIG)
    cand_idx = None  # top-level slots are their own indices
    cand_ok = None

    for l in range(L, 0, -1):
        lv = levels[l]
        if l == L:
            beam = min(beams[l], n_top)
            neg, slot = jax.lax.top_k(-D_top, beam)
            d_sel, sel_idx = -neg, slot.astype(jnp.int32)
        else:
            W = cand_idx.shape[1]
            beam = min(beams[l], W)
            d_sel, slot = kops.rank_gathered(  # [B, beam] fused rank
                Q, lv.points, lv.sq_norm, cand_idx, cand_ok, dist, k=beam,
                run_starts=starts, config=kernel,
            )
            sel_idx = jnp.take_along_axis(cand_idx, slot, axis=1)
        sel_ok = (d_sel < radii[l]) & (d_sel < BIG / 2)

        starts = jnp.take(lv.child_start, sel_idx)  # [B, beam]
        counts = jnp.take(lv.child_count, sel_idx)
        mc = max_children[l]
        grid = starts[:, :, None] + jnp.arange(mc, dtype=jnp.int32)[None, None, :]
        gvalid = (
            jnp.arange(mc)[None, None, :] < counts[:, :, None]
        ) & sel_ok[:, :, None]
        n_lower = levels[l - 1].points.shape[0]
        cand_idx = jnp.clip(grid.reshape(B, beam * mc), 0, n_lower - 1)
        cand_ok = gvalid.reshape(B, beam * mc)
    return cand_idx, cand_ok, starts


@functools.partial(
    jax.jit,
    static_argnames=("dist", "r", "beam", "max_children", "kernel"),
)
def descend_beam(
    index: PDASCIndexData,
    Q: Array,  # [B, d]
    *,
    dist: dist_lib.Distance,
    r,
    beam,
    max_children: tuple,
    kernel: Optional[kops.KernelConfig] = None,
) -> tuple[Array, Array]:
    """Public jitted beam descent: NSA levels L..1 without the leaf ranking.

    Returns ``(cand_idx [B, W], cand_ok [B, W])`` — the leaf candidate rows
    each query would rank. This is stage 0 of the two-stage tiered-store
    search (DESIGN.md §3.6); ``search_beam`` is exactly this followed by one
    fused fp32 leaf rank.
    """
    n_levels = len(index.levels)
    radii = _per_level_radii(r, n_levels)
    beams = tuple(int(b) for b in _per_level_radii(beam, n_levels))
    if n_levels == 1:  # degenerate: every valid leaf slot is a candidate
        n0 = index.levels[0].points.shape[0]
        B = Q.shape[0]
        cand_idx = jnp.broadcast_to(
            jnp.arange(n0, dtype=jnp.int32)[None, :], (B, n0)
        )
        cand_ok = jnp.broadcast_to(index.levels[0].valid[None, :], (B, n0))
        return cand_idx, cand_ok
    cand_idx, cand_ok, _ = _descend_beam(
        index, dist, Q, radii, beams, tuple(max_children),
        kernel or kops.DEFAULT,
    )
    return cand_idx, cand_ok


def assemble_result(
    index: PDASCIndexData,
    dists: Array,  # [B, k_eff] ascending leaf-rank output
    slots: Array,  # [B, k_eff] leaf slot indices
    ok: Array,  # [B, W] candidates examined (the pruning metric)
    *,
    k: int,
    leaf_radius: float,
    leaf_radius_filter: bool,
) -> SearchResult:
    """Shared result-assembly tail of every leaf-ranking mode (fused beam
    rank and the tiered-store rerank): radius masking, slot -> dataset-row
    id translation, candidate counting, and padding out to ``k`` when the
    candidate pool was smaller."""
    if leaf_radius_filter:
        dists = jnp.where(dists < leaf_radius, dists, BIG)
    ids = jnp.where(dists < BIG / 2, jnp.take(index.leaf_ids, slots), -1)
    n_cand = jnp.sum(ok, axis=1, dtype=jnp.int32)
    k_eff = dists.shape[1]
    if k_eff < k:  # tiny index edge case: fewer candidate slots than k
        pad = k - k_eff
        dists = jnp.pad(dists, ((0, 0), (0, pad)), constant_values=BIG)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    return SearchResult(dists=dists, ids=ids, n_candidates=n_cand)


def _search_beam_batch(
    index: PDASCIndexData,
    dist: dist_lib.Distance,
    Q: Array,  # [B, d]
    k: int,
    radii: tuple,
    beams: tuple,
    max_children: tuple,
    leaf_radius_filter: bool,
    kernel: kops.KernelConfig,
    slot_valid: Optional[Array] = None,
) -> SearchResult:
    """Whole-batch beam search: the descent (``_descend_beam``) followed by
    one fused fp32 leaf ranking. ``slot_valid`` (tombstones) masks leaf
    slots out of the ranking only — the descent stays frozen."""
    levels = index.levels
    L = len(levels) - 1
    B = Q.shape[0]

    leaf = levels[0]
    if L == 0:  # degenerate single-level index: the leaf is the top
        W = leaf.points.shape[0]
        D_top = kops.pairwise_distance(Q, leaf.points, dist, config=kernel)
        live = (leaf.valid if slot_valid is None
                else leaf.valid & slot_valid)
        D_top = jnp.where(live[None, :], D_top, BIG)
        ok = jnp.broadcast_to(live[None, :], (B, W))
        k_eff = min(k, W)
        neg, slot = jax.lax.top_k(-D_top, k_eff)
        dists, slots = -neg, slot.astype(jnp.int32)
    else:
        cand_idx, cand_ok, starts = _descend_beam(
            index, dist, Q, radii, beams, max_children, kernel
        )
        W = cand_idx.shape[1]
        ok = kref.fold_slot_valid(cand_idx, cand_ok, slot_valid)
        k_eff = min(k, W)
        dists, slot = kops.rank_gathered(  # fused leaf ranking
            Q, leaf.points, leaf.sq_norm, cand_idx, ok, dist, k=k_eff,
            run_starts=starts, config=kernel,
        )
        slots = jnp.take_along_axis(cand_idx, slot, axis=1)
    # Candidates counted are those *examined* (the pruning metric). The fused
    # kernel never materialises the full leaf distance vector, so with
    # leaf_radius_filter this counts examined rather than in-radius candidates.
    return assemble_result(
        index, dists, slots, ok, k=k, leaf_radius=radii[0],
        leaf_radius_filter=leaf_radius_filter,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "dist", "k", "r", "beam", "max_children", "leaf_radius_filter",
        "kernel",
    ),
)
def search_beam(
    index: PDASCIndexData,
    Q: Array,
    *,
    dist: dist_lib.Distance,
    k: int = 10,
    r,
    beam,
    max_children: tuple,
    leaf_radius_filter: bool = False,
    kernel: Optional[kops.KernelConfig] = None,
    slot_valid: Optional[Array] = None,
) -> SearchResult:
    """Batched beam NSA — the serving hot path.

    Args:
      beam: int or per-level tuple — surviving prototypes per level.
      max_children: static per-level max cluster size
        (:func:`repro.core.msa.max_children`).
      kernel: kernel-layer block knobs (None = defaults).
      slot_valid: optional bool[n_0] tombstone mask over leaf slots (True =
        live, DESIGN.md §3.7). Deleted slots rank as ``BIG`` at the leaf
        step; the beam descent over the (frozen) navigation tier is
        unchanged.
    """
    n_levels = len(index.levels)
    radii = _per_level_radii(r, n_levels)
    beams = _per_level_radii(beam, n_levels)
    beams = tuple(int(b) for b in beams)
    squeeze = Q.ndim == 1
    Qb = Q[None, :] if squeeze else Q
    res = _search_beam_batch(
        index,
        dist,
        Qb,
        k=k,
        radii=radii,
        beams=beams,
        max_children=tuple(max_children),
        leaf_radius_filter=leaf_radius_filter,
        kernel=kernel or kops.DEFAULT,
        slot_valid=slot_valid,
    )
    if squeeze:
        res = jax.tree.map(lambda a: a[0], res)
    return res


# ---------------------------------------------------------------------------
# Legacy per-query beam (seed baseline; kept for benchmarks and as an oracle)
# ---------------------------------------------------------------------------


def _search_beam_single(
    index: PDASCIndexData,
    dist: dist_lib.Distance,
    q: Array,
    k: int,
    radii: tuple,
    beams: tuple,
    max_children: tuple,
    leaf_radius_filter: bool,
) -> SearchResult:
    levels = index.levels
    L = len(levels) - 1

    # Start with every top-level prototype as a candidate.
    n_top = levels[L].points.shape[0]
    cand_idx = jnp.arange(n_top, dtype=jnp.int32)
    cand_ok = levels[L].valid

    for l in range(L, 0, -1):
        lv = levels[l]
        pts = jnp.take(lv.points, cand_idx, axis=0)
        d = dist.point(q[None, :], pts)
        ok = cand_ok & (d < radii[l])
        d_masked = jnp.where(ok, d, BIG)

        beam = min(beams[l], cand_idx.shape[0])
        neg, sel = jax.lax.top_k(-d_masked, beam)
        sel_idx = jnp.take(cand_idx, sel)
        sel_ok = -neg < BIG / 2

        starts = jnp.take(lv.child_start, sel_idx)
        counts = jnp.take(lv.child_count, sel_idx)
        mc = max_children[l]
        grid = starts[:, None] + jnp.arange(mc, dtype=jnp.int32)[None, :]
        gvalid = (jnp.arange(mc)[None, :] < counts[:, None]) & sel_ok[:, None]
        n_lower = levels[l - 1].points.shape[0]
        cand_idx = jnp.clip(grid.reshape(-1), 0, n_lower - 1)
        cand_ok = gvalid.reshape(-1)

    leaf = levels[0]
    pts = jnp.take(leaf.points, cand_idx, axis=0)
    d = dist.point(q[None, :], pts)
    ok = cand_ok
    if leaf_radius_filter:
        ok = ok & (d < radii[0])
    d_masked = jnp.where(ok, d, BIG)

    dists, slot_pos = jax.lax.top_k(-d_masked, min(k, d_masked.shape[0]))
    dists = -dists
    slots = jnp.take(cand_idx, slot_pos)
    ids = jnp.where(dists < BIG / 2, jnp.take(index.leaf_ids, slots), -1)
    if dists.shape[0] < k:  # tiny index edge case
        pad = k - dists.shape[0]
        dists = jnp.pad(dists, (0, pad), constant_values=BIG)
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    return SearchResult(
        dists=dists, ids=ids, n_candidates=jnp.sum(ok, dtype=jnp.int32)
    )


@functools.partial(
    jax.jit,
    static_argnames=("dist", "k", "r", "beam", "max_children", "leaf_radius_filter"),
)
def search_beam_vmap(
    index: PDASCIndexData,
    Q: Array,
    *,
    dist: dist_lib.Distance,
    k: int = 10,
    r,
    beam,
    max_children: tuple,
    leaf_radius_filter: bool = False,
) -> SearchResult:
    """The seed per-query beam NSA (vmap of scalar ``dist.point`` searches).

    Superseded by :func:`search_beam`; retained as the benchmark baseline
    and as an independent oracle for the batched path's tests.
    """
    n_levels = len(index.levels)
    radii = _per_level_radii(r, n_levels)
    beams = _per_level_radii(beam, n_levels)
    beams = tuple(int(b) for b in beams)
    single = functools.partial(
        _search_beam_single,
        index,
        dist,
        k=k,
        radii=radii,
        beams=beams,
        max_children=tuple(max_children),
        leaf_radius_filter=leaf_radius_filter,
    )
    if Q.ndim == 1:
        return single(q=Q)
    return jax.vmap(lambda q: single(q=q))(Q)
