"""The kernel-layer build substrate (DESIGN.md §3.5): eager multi-swap
FasterPAM properties, the fused Pallas swap-sweep kernel vs its oracle,
group-chunked streaming memory honesty, level-loop termination, and the
end-to-end seed-vs-new build guard."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _jaxpr_walk import max_outvar_elems, pallas_bodies

from repro.core import distances as dl
from repro.core import kmedoids as km
from repro.core import msa, nsa
from repro.data import make_dataset
from repro.kernels import ops
from repro.kernels.ref import knn_ref, swap_deltas_ref


def _pairwise(X, name="euclidean"):
    X = jnp.asarray(X)
    return jnp.asarray(np.asarray(dl.get(name).pairwise(X, X)))


# ---------------------------------------------------------------------------
# Eager multi-swap FasterPAM properties
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_eager_sweep_td_monotone(seed):
    """TD never increases across eager sweeps, and the carried TD matches an
    exact recompute after every sweep (the single-swap fallback guard)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(70, 4)).astype(np.float32)
    D = _pairwise(X, "manhattan")
    valid = jnp.ones((70,), bool)
    medoids = km.build(D, 10, valid)
    _, td = km._labels_and_td(D, medoids, valid)
    for _ in range(12):
        medoids, td, _, improving = km.sweep_once(D, valid, medoids, td)
        _, td_exact = km._labels_and_td(D, medoids, valid)
        np.testing.assert_allclose(float(td), float(td_exact), rtol=1e-5)
        if not bool(improving):
            break
    assert not bool(improving), "swap loop must converge within the budget"


def test_eager_final_td_not_worse_than_seed_loop():
    """Both loops stop when no single swap improves, so both end at
    single-swap local optima — the eager one must be at least as good on
    average over random instances, and never more than a whisker worse on
    any one (different accept order => occasionally a different, near-equal
    optimum)."""
    news, refs = [], []
    for seed in range(8):
        rng = np.random.default_rng(100 + seed)
        g, k = 90, 14
        X = rng.normal(size=(g, 5)).astype(np.float32)
        D = _pairwise(X)
        new = km.kmedoids(D, k=k, method="pam")
        ref = km.kmedoids(D, k=k, method="pam_reference")
        news.append(float(new.td))
        refs.append(float(ref.td))
        assert news[-1] <= refs[-1] * 1.005 + 1e-5, (seed, news[-1], refs[-1])
    assert np.mean(news) <= np.mean(refs) + 1e-4, (news, refs)


def test_eager_swap_masked_padding():
    """Padding points are never swapped in by the eager accept."""
    rng = np.random.default_rng(7)
    X = np.concatenate(
        [rng.normal(size=(40, 3)), np.full((12, 3), 1e3)]
    ).astype(np.float32)
    D = _pairwise(X)
    valid = jnp.asarray([True] * 40 + [False] * 12)
    res = km.kmedoids(D, k=6, valid=valid, method="pam")
    med = np.asarray(res.medoids)
    assert (med[med >= 0] < 40).all()


def test_build_grouped_matches_scalar_build():
    """The batched [G, g, g] BUILD contraction reproduces the per-group
    greedy BUILD exactly (same argmin tie order)."""
    rng = np.random.default_rng(9)
    Xg = rng.normal(size=(5, 24, 3)).astype(np.float32)
    Dg = jnp.stack([_pairwise(x, "cosine") for x in Xg])
    valid = jnp.asarray(rng.random((5, 24)) > 0.2)
    grouped = km.build_grouped(Dg, 6, valid)
    for i in range(5):
        single = km.build(Dg[i], 6, valid[i])
        np.testing.assert_array_equal(np.asarray(grouped[i]), np.asarray(single))


# ---------------------------------------------------------------------------
# Fused swap-sweep kernel: interpret-mode Pallas vs the ref.py oracle
# ---------------------------------------------------------------------------

SWEEP_SHAPES = [(20, 5, 8), (64, 32, 16), (33, 7, 128), (130, 65, 32),
                (256, 128, 64)]


@pytest.mark.parametrize("g,k,bg", SWEEP_SHAPES)
def test_swap_deltas_kernel_interpret_parity(g, k, bg):
    rng = np.random.default_rng(g * 7 + k)
    X = rng.normal(size=(g, 4)).astype(np.float32)
    D = _pairwise(X)
    valid = jnp.asarray(rng.random(g) > 0.2)
    medoids = km.build(D, k, valid)
    d1, n1, d2 = km._nearest_caches(D, medoids, valid)
    want = swap_deltas_ref(D, d1, d2, n1, valid, k)
    got = ops.swap_deltas(D, d1, d2, n1, valid, k=k, bg=bg, force_pallas=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_swap_deltas_kernel_vmapped_parity():
    """vmap over a groups axis (the MSA layout) lifts into the kernel grid."""
    rng = np.random.default_rng(21)
    Xg = rng.normal(size=(3, 40, 4)).astype(np.float32)
    Dg = jnp.stack([_pairwise(x) for x in Xg])
    valid = jnp.ones((3, 40), bool)
    med = jax.vmap(lambda D, v: km.build(D, 9, v))(Dg, valid)
    d1, n1, d2 = jax.vmap(km._nearest_caches)(Dg, med, valid)
    got = jax.vmap(
        lambda D, a, b, c, v: ops.swap_deltas(
            D, a, b, c, v, k=9, bg=16, force_pallas=True
        )
    )(Dg, d1, d2, n1, valid)
    want = jax.vmap(lambda D, a, b, c, v: swap_deltas_ref(D, a, b, c, v, 9))(
        Dg, d1, d2, n1, valid
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Memory honesty (jaxpr scans, mirroring test_dense_l1_never_materialises_cube)
# ---------------------------------------------------------------------------


def test_chunked_build_never_materialises_all_group_matrices():
    """With group_chunk streaming, no intermediate of the traced MSA build
    reaches [G, g, g] elements: the clustering working set is bounded by
    [group_chunk, g, g] however many groups the level holds."""
    n, d, gl, gc = 2048, 4, 64, 4
    G = n // gl  # 32 >> group_chunk
    data = jnp.zeros((n, d), jnp.float32)
    closed = jax.make_jaxpr(
        lambda x: msa.build_index_arrays(
            x, gl=gl, distance="euclidean", method="pam", group_chunk=gc
        )
    )(data)
    seen = max_outvar_elems(closed.jaxpr)
    assert seen < G * gl * gl, (seen, G * gl * gl)
    assert seen <= gc * gl * gl, (seen, gc * gl * gl)


def test_sweep_kernel_streams_row_tiles():
    """Inside the Pallas sweep-kernel body nothing larger than one streamed
    [bg, g] tile / the persistent [k, g] accumulator exists — the [g, g]
    gain/removal matrices of the oracle are never materialised."""
    g, k, bg = 256, 16, 16
    rng = np.random.default_rng(3)
    X = rng.normal(size=(g, 4)).astype(np.float32)
    D = _pairwise(X)
    valid = jnp.ones((g,), bool)
    medoids = km.build(D, k, valid)
    d1, n1, d2 = km._nearest_caches(D, medoids, valid)
    closed = jax.make_jaxpr(
        lambda *a: ops.swap_deltas(*a, k=k, bg=bg, force_pallas=True)
    )(D, d1, d2, n1, valid)

    # Scan only the pallas_call's kernel-body jaxpr.
    bodies = pallas_bodies(closed.jaxpr)
    assert bodies, "no pallas_call in the traced sweep"
    gc_pad = -(-g // 128) * 128
    kp = -(-k // 8) * 8
    tile_bound = max(bg, kp) * gc_pad
    for body in bodies:
        seen = max_outvar_elems(body)
        assert seen <= tile_bound < g * g, (seen, tile_bound, g * g)


# ---------------------------------------------------------------------------
# Level-loop termination (regression: k == gl used to loop forever)
# ---------------------------------------------------------------------------


def test_n_prototypes_equal_gl_raises():
    rng = np.random.default_rng(11)
    data = rng.normal(size=(100, 4)).astype(np.float32)
    with pytest.raises(ValueError, match="never reduces"):
        msa.build_index(data, gl=10, n_prototypes=10)
    with pytest.raises(ValueError, match="never reduces"):
        msa.n_levels_for(100, 10, 10)


def test_n_prototypes_above_half_gl_raises():
    """Any k > gl // 2 sticks at >= 2 groups (ceil(2k/gl) == 2), not just
    k == gl."""
    with pytest.raises(ValueError, match="never reduces"):
        msa.n_levels_for(1000, 10, 6)


def test_single_group_allows_k_up_to_gl():
    """n <= gl is one group clustered once; k == gl just promotes all."""
    assert msa.n_levels_for(20, 32, 32) == 1
    rng = np.random.default_rng(12)
    data = rng.normal(size=(20, 4)).astype(np.float32)
    idx, stats = msa.build_index(data, gl=32, n_prototypes=32)
    assert stats.level_sizes == (20, 20)


# ---------------------------------------------------------------------------
# End-to-end guard: new-built index serves like the seed-built index
# ---------------------------------------------------------------------------


def _recall(ids, gt):
    return np.mean(
        [len(set(ids[i].tolist()) & set(gt[i].tolist())) / gt.shape[1]
         for i in range(len(gt))]
    )


def test_new_build_matches_seed_build_recall():
    """Same key => same shuffle => same grouping: the eager-swap, chunked
    build must yield the seed level structure, a final TD within 1%, and
    dense/beam search recall within noise of the seed-built index."""
    data = make_dataset("dense_embed", n=1560, seed=0).astype(np.float32)
    data = data[:, :16]
    key = jax.random.PRNGKey(0)
    seed_idx, seed_stats = msa.build_index(
        data, gl=64, method="pam_reference", group_chunk=0, key=key
    )
    new_idx, new_stats = msa.build_index(
        data, gl=64, method="pam", group_chunk=4, key=key
    )
    assert new_stats.level_sizes == seed_stats.level_sizes
    assert new_stats.level_td[0] <= seed_stats.level_td[0] * 1.01

    dist = dl.get("euclidean")
    Q = jnp.asarray(data[:64])
    _, gt = knn_ref(Q, jnp.asarray(data), 10, "l2")
    gt = np.asarray(gt)
    r = 1.15 * float(np.median(np.asarray(
        dl.get("euclidean").pairwise(Q, jnp.asarray(data))
    )))
    recs = {}
    for name, idx in (("seed", seed_idx), ("new", new_idx)):
        dres = nsa.search_dense(idx, Q, dist=dist, k=10, r=r)
        bres = nsa.search_beam(idx, Q, dist=dist, k=10, r=r, beam=32,
                               max_children=msa.max_children(idx))
        recs[name, "dense"] = _recall(np.asarray(dres.ids), gt)
        recs[name, "beam"] = _recall(np.asarray(bres.ids), gt)
    for mode in ("dense", "beam"):
        assert abs(recs["new", mode] - recs["seed", mode]) < 0.05, recs
    assert recs["new", "dense"] > 0.8, recs


@pytest.mark.parametrize("method", ["pam", "kmeans"])
def test_chunked_build_equals_dense_build(method):
    """group_chunk only changes the execution schedule: the chunked build
    returns the same index as the whole-level build (same key, same
    arithmetic per group — for kmeans that includes the per-group PRNG
    keys, which must not depend on the chunk padding)."""
    rng = np.random.default_rng(13)
    data = rng.normal(size=(600, 5)).astype(np.float32)
    key = jax.random.PRNGKey(3)
    a, _ = msa.build_index(data, gl=32, method=method, group_chunk=0, key=key)
    b, _ = msa.build_index(data, gl=32, method=method, group_chunk=3, key=key)
    for la, lb in zip(a.levels, b.levels):
        np.testing.assert_array_equal(np.asarray(la.valid), np.asarray(lb.valid))
        np.testing.assert_allclose(
            np.asarray(la.points), np.asarray(lb.points), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_array_equal(np.asarray(la.parent), np.asarray(lb.parent))
    np.testing.assert_array_equal(np.asarray(a.leaf_ids), np.asarray(b.leaf_ids))


def test_build_end_to_end_force_pallas():
    """A full MSA build with force_pallas=True runs the Pallas sweep-kernel
    body (interpret mode) on every swap sweep and lands on the same level
    structure and TD (to fp tolerance) as the oracle dispatch."""
    rng = np.random.default_rng(15)
    data = rng.normal(size=(300, 5)).astype(np.float32)
    key = jax.random.PRNGKey(4)
    ref_idx, ref_stats = msa.build_index(data, gl=32, key=key, bg=16)
    pal_idx, pal_stats = msa.build_index(data, gl=32, key=key, bg=16,
                                         force_pallas=True)
    assert ref_stats.level_sizes == pal_stats.level_sizes
    for a, b in zip(ref_stats.level_td, pal_stats.level_td):
        np.testing.assert_allclose(a, b, rtol=1e-3)


def test_kmeans_chunked_relabel_valid():
    """kmeans path under chunking: labels index medoid slots and the index
    invariants hold (relabel now computes [g, k] against snapped medoids
    through the kernel layer)."""
    from repro.core.reference_impl import check_index_invariants

    rng = np.random.default_rng(14)
    data = rng.normal(size=(400, 6)).astype(np.float32)
    idx, stats = msa.build_index(data, gl=40, method="kmeans", group_chunk=3)
    assert check_index_invariants(idx) == []
    assert stats.level_sizes[0] == 400
