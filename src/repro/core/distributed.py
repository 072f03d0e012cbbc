"""Distributed PDASC: sharded build, sharded search, global top-k merge.

The paper's deployment model (§3.1): the dataset is randomly partitioned
across computational nodes; each node clusters its own groups; a query fans
out to the nodes and the per-node results are combined. On a TPU mesh this
maps to (DESIGN.md §3.4):

* **build**  — ``shard_map`` over the database axes: every device runs MSA on
  its local shard and owns an independent sub-index (exactly the paper's
  "groups distributed across nodes" — a PDASC index *is* a forest of
  per-partition trees; stacking sub-indexes adds one more implicit level).
* **search** — queries are replicated across the database axes (each device
  answers against its shard), then the per-device top-k are merged globally.
* **storage** — with a tiered leaf store (DESIGN.md §3.6) the navigation
  tier replicates while the quantised payload shards by leaf-row range:
  ``shard_payload`` slices codes + scales per node and
  ``scan_quantized_sharded`` runs the stage-1 scan locally, merging
  survivors with the same top-k collectives.

Top-k merge operators (the collective hot path):

``topk_merge_allgather``
    one ``all_gather`` of ``[B, k]`` pairs -> every device selects from
    ``P*k`` candidates. Bytes received per device: ``(P-1) * B * k * 8``.

``topk_merge_butterfly``
    recursive-halving butterfly: ``log2(P)`` ``ppermute`` rounds, each
    exchanging exactly ``B * k`` pairs with the round's partner and merging.
    Bytes received per device: ``log2(P) * B * k * 8`` — an ``(P-1)/log2(P)``x
    reduction (e.g. 51x at P=256). This is the beyond-paper collective
    optimisation benchmarked in EXPERIMENTS.md §Perf.

Hierarchical meshes merge axis-by-axis (fast intra-pod axis first, then the
slow ``pod`` axis), so inter-pod traffic is a single butterfly at ``B * k``
pairs per hop.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import distances as dist_lib
from repro.core import msa, nsa
from repro.kernels import ops as kops
from repro.kernels import ref as kref

Array = jax.Array

# ---------------------------------------------------------------------------
# Global top-k merge collectives
# ---------------------------------------------------------------------------


def topk_merge_allgather(dists: Array, ids: Array, axis_name: str, k: int):
    """Naive merge: all_gather every shard's [B, k] then select."""
    gd = jax.lax.all_gather(dists, axis_name, axis=0)  # [P, B, k]
    gi = jax.lax.all_gather(ids, axis_name, axis=0)
    Pn = gd.shape[0]
    gd = jnp.moveaxis(gd, 0, -2).reshape(*dists.shape[:-1], Pn * k)
    gi = jnp.moveaxis(gi, 0, -2).reshape(*ids.shape[:-1], Pn * k)
    neg, idx = jax.lax.top_k(-gd, k)
    return -neg, jnp.take_along_axis(gi, idx, axis=-1)


def topk_merge_butterfly(dists: Array, ids: Array, axis_name: str, k: int):
    """Butterfly (recursive-doubling) merge: log2(P) ppermute rounds.

    After round t every device holds the top-k over its 2^(t+1)-device
    sub-cube; after log2(P) rounds all devices hold the global top-k
    (replicated). Requires a power-of-two axis size.
    """
    Pn = jax.lax.axis_size(axis_name)
    if Pn & (Pn - 1):
        raise ValueError(f"butterfly merge needs power-of-two axis, got {Pn}")
    rounds = int(math.log2(Pn))
    for t in range(rounds):
        perm = [(i, i ^ (1 << t)) for i in range(Pn)]
        od = jax.lax.ppermute(dists, axis_name, perm)
        oi = jax.lax.ppermute(ids, axis_name, perm)
        cd = jnp.concatenate([dists, od], axis=-1)
        ci = jnp.concatenate([ids, oi], axis=-1)
        neg, idx = jax.lax.top_k(-cd, k)
        dists = -neg
        ids = jnp.take_along_axis(ci, idx, axis=-1)
    return dists, ids


def topk_merge(dists, ids, axis_names: Sequence[str], k: int, *, method="butterfly"):
    """Merge across several mesh axes, fastest axis first."""
    fn = topk_merge_butterfly if method == "butterfly" else topk_merge_allgather
    for ax in axis_names:
        dists, ids = fn(dists, ids, ax, k)
    return dists, ids


# ---------------------------------------------------------------------------
# Sharded MSA build
# ---------------------------------------------------------------------------


def _axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _shard_index(axes: Sequence[str]):
    """Linear shard index across (possibly several) mesh axes."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def build_sharded(
    data: Array,
    mesh: Mesh,
    *,
    db_axes: Sequence[str] = ("data",),
    gl: int,
    n_prototypes: Optional[int] = None,
    distance="euclidean",
    method: str = "pam",
    max_swaps: int = 64,
    key: Optional[Array] = None,
    row_chunk: int = 512,
    group_chunk: int = 8,
    swap_tol: float = 1e-3,
    bg: int = 128,
):
    """Build one PDASC sub-index per device shard.

    ``data``: [n, d] with ``n`` divisible by the product of ``db_axes`` sizes.
    Returns a stacked ``PDASCIndexData`` whose every leaf has a leading
    per-shard axis of size P (sharded over ``db_axes``). ``group_chunk``
    bounds each shard's clustering working set at O(group_chunk · gl²) —
    the per-node memory budget of the paper's deployment model.
    """
    Pn = _axes_size(mesh, db_axes)
    n, d = data.shape
    if n % Pn:
        raise ValueError(f"n={n} not divisible by shard count {Pn}")
    per = n // Pn
    key = key if key is not None else jax.random.PRNGKey(0)
    spec_in = P(tuple(db_axes), None, None)

    def _build_local(local, k_local):  # local: [1, per, d]
        index, _ = msa.build_index_arrays(
            local[0],
            gl=gl,
            n_prototypes=n_prototypes,
            distance=distance,
            method=method,
            max_swaps=max_swaps,
            key=k_local,
            row_chunk=row_chunk,
            group_chunk=group_chunk,
            swap_tol=swap_tol,
            bg=bg,
        )
        return jax.tree.map(lambda a: a[None], index)

    def body(local):
        shard = _shard_index(db_axes)
        return _build_local(local, jax.random.fold_in(key, shard))

    # out_specs: same tree as the body's output, every leaf sharded over the
    # database axes (evaluated without the axis_index, which needs the mesh).
    shape_tree = jax.eval_shape(
        functools.partial(_build_local, k_local=key),
        jax.ShapeDtypeStruct((1, per, d), jnp.float32),
    )
    out_spec = jax.tree.map(lambda _: P(tuple(db_axes)), shape_tree)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec_in,),
                       out_specs=out_spec, check_vma=False)
    return fn(data.reshape(Pn, per, d).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Sharded NSA search
# ---------------------------------------------------------------------------


# Bounded: each entry pins its Mesh + compiled executable, and a long-lived
# process may cycle meshes/knobs — eviction merely costs the old per-call
# retrace for that config, never correctness.
@functools.lru_cache(maxsize=64)
def _sharded_search_fn(
    mesh: Mesh,
    db_axes: tuple,
    dist,
    k: int,
    r,
    mode: str,
    beam,
    max_children: Optional[tuple],
    merge: str,
    leaf_radius_filter: bool,
    with_stats: bool,
    kernel,
    has_mask: bool,
):
    """Build (once per static config) the jitted shard_map executor behind
    :func:`search_sharded`.

    The cache is what makes repeated sharded execution retrace-free: the
    pre-refactor code rebuilt the ``shard_map`` closure per call, so every
    search re-traced the whole per-shard program. Keyed on every static
    knob (all hashable — the same values the per-shard jits key on), the
    returned callable is one ``jax.jit`` whose own cache then keys on input
    shapes/dtypes only.
    """

    def body(index_stacked, Qr, *sv):
        index = jax.tree.map(lambda a: a[0], index_stacked)
        sv_local = sv[0][0] if sv else None
        shard = _shard_index(db_axes)
        if mode == "dense":
            res = nsa.search_dense(
                index, Qr, dist=dist, k=k, r=r,
                leaf_radius_filter=leaf_radius_filter, with_stats=with_stats,
                kernel=kernel, slot_valid=sv_local,
            )
        else:
            res = nsa.search_beam(
                index, Qr, dist=dist, k=k, r=r, beam=beam,
                max_children=max_children, leaf_radius_filter=leaf_radius_filter,
                kernel=kernel, slot_valid=sv_local,
            )
        # leaf_ids are local rows of this shard's slice; lift to global rows.
        # NOTE: the shard's local shuffle permutes only within the shard, so
        # global_row = shard * per_shard_n + local_row.
        per_shard_n = jnp.int32(index_stacked.leaf_ids.shape[1])
        gids = jnp.where(res.ids >= 0, res.ids + shard * per_shard_n, -1)
        d_m, i_m = topk_merge(res.dists, gids, tuple(db_axes), k, method=merge)
        nc = jax.lax.psum(res.n_candidates, tuple(db_axes))
        return nsa.SearchResult(dists=d_m, ids=i_m, n_candidates=nc)

    # Prefix specs: the index arg's single P broadcasts over its whole tree.
    in_specs = [P(db_axes), P()]  # sharded index, replicated queries
    if has_mask:
        in_specs.append(P(db_axes))  # mask sharded like the index
    out_specs = nsa.SearchResult(dists=P(), ids=P(), n_candidates=P())
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                      out_specs=out_specs, check_vma=False)
    )


def search_sharded(
    sharded_index: msa.PDASCIndexData,
    Q: Array,
    mesh: Mesh,
    *,
    db_axes: Sequence[str] = ("data",),
    dist,
    k: int = 10,
    r,
    mode: str = "dense",
    beam: int = 32,
    max_children: Optional[tuple] = None,
    merge: str = "butterfly",
    leaf_radius_filter: bool = False,
    with_stats: bool = True,
    kernel: Optional[kops.KernelConfig] = None,
    slot_valid: Optional[Array] = None,
) -> nsa.SearchResult:
    """Distributed NSA: per-shard search + global top-k merge.

    Queries are replicated over ``db_axes`` (every shard answers against its
    own sub-index); returned ids are *global* dataset rows (shard-offset
    applied). Output is replicated. ``kernel`` (block knobs) reaches the
    kernel layer through the per-shard search. ``slot_valid``: optional
    ``[P, n_leaf_local]`` tombstone mask, sharded like the index — each node
    masks its own deleted leaf slots before its local rank, so deleted ids
    never enter the merge (DESIGN.md §3.7; build per-shard masks from global
    ids with :func:`route_writes` + :func:`local_slot_valid`).

    This is the execution substrate of the query layer's sharded pipeline
    (``repro.query.compile_sharded_plan``); the executor is compiled once
    per static configuration (:func:`_sharded_search_fn`), so repeated
    calls — and repeated sharded-plan executions — never retrace.
    """
    dist = dist_lib.get(dist)

    def _freeze(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v

    fn = _sharded_search_fn(
        mesh, tuple(db_axes), dist, k, _freeze(r), mode, _freeze(beam),
        tuple(max_children) if max_children is not None else None, merge,
        leaf_radius_filter, with_stats, kernel, slot_valid is not None,
    )
    args = [sharded_index, jnp.asarray(Q)]
    if slot_valid is not None:
        args.append(jnp.asarray(slot_valid))
    # keep the caller's dtype: bf16 queries + bf16 index points -> bf16
    # distance math (the §Perf H3 memory-halving path)
    return fn(*args)


# ---------------------------------------------------------------------------
# Sharded payload tier (tiered leaf store, DESIGN.md §3.6)
# ---------------------------------------------------------------------------


def shard_payload(store, mesh: Mesh, *, db_axes: Sequence[str] = ("data",)):
    """Split a quantised payload tier across the database axes.

    The storage-aware deployment keeps the *navigation* tier (prototype
    levels) replicated on every node — it is small and every query walks it
    — while the payload codes shard by leaf-row range: node ``p`` owns rows
    ``[p*per, (p+1)*per)`` and the matching slice of the per-block scales.
    Returns ``(codes [P, per, d], scales [P, nb_per])`` ready for
    ``shard_map`` over ``db_axes`` (:func:`scan_quantized_sharded`).
    """
    if store.backend == "fp32" or store.codes is None:
        raise ValueError(
            "shard_payload needs a quantised store (int8/fp16/int4/binary)"
        )
    Pn = _axes_size(mesh, db_axes)
    n, d = store.codes.shape
    if n % Pn:
        raise ValueError(f"payload rows n={n} not divisible by shards {Pn}")
    per = n // Pn
    if per % store.block:
        raise ValueError(
            f"per-shard rows {per} not granule-aligned (block={store.block}); "
            f"scales cannot shard cleanly"
        )
    nb_per = per // store.block
    return (
        store.codes.reshape(Pn, per, d),
        store.scales.reshape(Pn, nb_per),
    )


def payload_placement(n: int, block: int, n_shards: int) -> list:
    """Granule co-placement map for a remote exact tier (DESIGN.md §3.13).

    The same row-range ownership :func:`shard_payload` gives the resident
    codes, expressed in *granule* coordinates: node ``p`` owns rows
    ``[p*per, (p+1)*per)`` and therefore granules
    ``[p*per//block, (p+1)*per//block)`` of the remote payload. Because
    granules never straddle shard boundaries (``per % block == 0``,
    enforced here exactly as in :func:`shard_payload`, and the streaming
    build aligns shard flushes the same way), a node's exact-rerank
    fetches only ever touch its own granule range — co-placement with the
    code shard, no cross-node payload traffic.

    Returns ``[dict(shard=p, rows=(lo, hi), granules=(g_lo, g_hi)), ...]``
    — half-open ranges. Use a node's ``granules`` range to warm its host
    LRU (``RemoteSource.prefetch_async(range(g_lo, g_hi))``) at placement
    time.
    """
    if n % n_shards:
        raise ValueError(f"payload rows n={n} not divisible by "
                         f"shards {n_shards}")
    per = n // n_shards
    if per % block:
        raise ValueError(
            f"per-shard rows {per} not granule-aligned (block={block}); "
            f"granules would straddle shard boundaries"
        )
    g_per = per // block
    return [
        dict(shard=p, rows=(p * per, (p + 1) * per),
             granules=(p * g_per, (p + 1) * g_per))
        for p in range(n_shards)
    ]


def scan_quantized_sharded(
    codes: Array,  # [P, per, d] from shard_payload
    scales: Array,  # [P, nb_per]
    Q: Array,  # [B, d] replicated queries
    cand_idx: Array,  # [B, W] *global* leaf rows (the replicated descent)
    cand_ok: Array,  # [B, W]
    mesh: Mesh,
    *,
    db_axes: Sequence[str] = ("data",),
    distance="l2",
    k: int,
    block: int,
    merge: str = "butterfly",
    kernel: Optional[kops.KernelConfig] = None,
    slot_valid: Optional[Array] = None,
    code_format: str = "dense",
):
    """Distributed stage-1 scan: each node scans the candidates it owns.

    The navigation descent is replicated (every node computes the same
    ``cand_idx``); each shard masks the candidate table to its own row
    range, scans its local codes, and the per-shard top-k merge with the
    same collectives as the search path. Returns ``(dists [B, k],
    slots [B, k])`` replicated, ``slots`` being *global* leaf rows (-1 for
    missing) — the input of the exact rerank fetch. ``slot_valid``:
    optional ``[P, per]`` tombstone mask sharded with the codes — each node
    drops its own deleted rows before the scan. ``code_format``: the store's
    packed-code layout (``"dense"`` | ``"int4"`` | ``"binary"``,
    ``LeafStore.code_format``) — shards carry packed containers and unpack
    per-tile exactly like the local scan.
    """
    kernel = kernel or kops.DEFAULT
    per = codes.shape[1]

    def body(codes_l, scales_l, Qr, ci, ok, *sv):
        shard = _shard_index(db_axes)
        lo = shard * jnp.int32(per)
        local_ok = ok & (ci >= lo) & (ci < lo + per)
        ci_local = jnp.clip(ci - lo, 0, per - 1)
        d, slot = kops.scan_quantized(
            Qr, codes_l[0], scales_l[0], ci_local, local_ok, distance,
            k=k, block=block, slot_valid=sv[0][0] if sv else None,
            code_format=code_format, config=kernel,
        )
        gslots = jnp.take_along_axis(ci, slot, axis=1)
        gslots = jnp.where(d < kref.BIG / 2, gslots, -1)
        return topk_merge(d, gslots, tuple(db_axes), k, method=merge)

    in_specs = [P(tuple(db_axes)), P(tuple(db_axes)), P(), P(), P()]
    args = [codes, scales, jnp.asarray(Q, jnp.float32), cand_idx, cand_ok]
    if slot_valid is not None:
        in_specs.append(P(tuple(db_axes)))
        args.append(jnp.asarray(slot_valid))
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(*args)


# ---------------------------------------------------------------------------
# Shard-by-id write routing (online substrate, DESIGN.md §3.7)
# ---------------------------------------------------------------------------


def route_writes(ids, n_shards: int, per_shard_n: int):
    """Route global dataset rows to the shard that owns them.

    The sharded deployment assigns row ranges: shard ``p`` owns global rows
    ``[p*per_shard_n, (p+1)*per_shard_n)`` — the same mapping
    :func:`search_sharded` uses to lift local ids to global ones, so writes
    (upserts / deletes by id) land on the node whose sub-index and payload
    slice hold the row. Returns ``[(shard, local_rows int64[m_p]), ...]``
    for the shards that receive at least one write (host-side: write routing
    is control plane, not a collective).
    """
    ids = np.asarray(ids, np.int64).reshape(-1)
    if ids.size and (ids.min() < 0 or ids.max() >= n_shards * per_shard_n):
        raise ValueError(
            f"write ids out of range [0, {n_shards * per_shard_n}) for "
            f"{n_shards} shards x {per_shard_n} rows"
        )
    shard = ids // per_shard_n
    return [
        (int(s), ids[shard == s] - int(s) * per_shard_n)
        for s in range(n_shards)
        if bool(np.any(shard == s))
    ]


def local_slot_valid(leaf_ids_local, deleted_local_rows):
    """Per-shard tombstone mask from locally-routed deleted rows.

    ``leaf_ids_local``: int32[n_0] — the shard's leaf-slot -> local-row map
    (one row of the stacked ``sharded_index.leaf_ids``).
    ``deleted_local_rows``: the shard's entry from :func:`route_writes`.
    Returns bool[n_0] (True = live) for ``search_sharded(slot_valid=...)``.
    """
    leaf_ids_local = np.asarray(leaf_ids_local)
    dead = np.zeros(int(leaf_ids_local.max(initial=0)) + 1, bool)
    rows = np.asarray(deleted_local_rows, np.int64)
    dead[rows[rows <= leaf_ids_local.max(initial=0)]] = True
    ok = ~dead[np.clip(leaf_ids_local, 0, dead.shape[0] - 1)]
    return ok | (leaf_ids_local < 0)  # padding slots stay "live" (invalid anyway)


# ---------------------------------------------------------------------------
# Distributed exact k-NN (ground truth / retrieval_cand scoring)
# ---------------------------------------------------------------------------


def exact_knn_sharded(
    DB: Array,
    Q: Array,
    mesh: Mesh,
    *,
    db_axes: Sequence[str] = ("data",),
    distance="l2",
    k: int = 10,
    merge: str = "butterfly",
):
    """Brute-force distributed k-NN: shard the database, replicate queries,
    per-shard fused distance+top-k, global merge. The exact baseline every
    recall number is measured against, and the ``retrieval_cand`` scorer."""
    form = distance if distance in kref.FORMS else None
    dist = None if form else dist_lib.get(distance)
    Pn = _axes_size(mesh, db_axes)
    n, d = DB.shape
    if n % Pn:
        raise ValueError(f"n={n} not divisible by {Pn}")
    per = n // Pn

    def body(db_local, Qr):
        db = db_local[0]
        shard = _shard_index(db_axes)
        if form is not None:
            D = kref.pairwise_ref(Qr, db, form)
        else:
            D = dist.pairwise(Qr, db)
        neg, idx = jax.lax.top_k(-D, k)
        gids = idx.astype(jnp.int32) + shard * jnp.int32(per)
        return topk_merge(-neg, gids, tuple(db_axes), k, method=merge)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(tuple(db_axes), None, None), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(DB.reshape(Pn, per, d), jnp.asarray(Q, jnp.float32))
