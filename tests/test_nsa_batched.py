"""Batched beam search through the kernel layer: exact equivalence with the
dense path, parity with the legacy vmap beam, and interpret-mode execution of
the fused rank kernel (gather -> distance -> top-k)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _jaxpr_walk import max_outvar_elems

from repro.core import distances as dl
from repro.core import msa, nsa, radius as rl
from repro.kernels import ops, ref as kref

# Every registry distance with a kernelised form (ops.resolve_form != None).
KERNEL_DISTANCES = ["euclidean", "manhattan", "chebyshev", "cosine", "dot"]


def _build(distance, n=240, d=6, gl=32, seed=3):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n, d)).astype(np.float32)
    idx, _ = msa.build_index(data, gl=gl, distance=distance,
                             key=jax.random.PRNGKey(seed))
    return data, idx


def _gap_radius(idx, dist, Q, quantile=0.6, min_gap=5e-3):
    """A radius sitting in a wide gap of the query-to-prototype distance
    distribution. Cross-implementation comparisons need this: two f32
    arithmetics that differ in the last ulps may disagree on ``d < r`` when
    some distance lands within that error of ``r``; a gapped radius makes
    the radius predicate implementation-independent."""
    ds = []
    for lv in idx.levels:
        D = np.asarray(dl.get(dist).pairwise(Q, lv.points))
        ds.append(D[:, np.asarray(lv.valid)].ravel())
    ds = np.unique(np.concatenate(ds))
    gaps = np.diff(ds)
    start = int(len(ds) * quantile)
    for j in range(start, len(gaps)):
        if gaps[j] > min_gap:
            return float((ds[j] + ds[j + 1]) / 2)
    return float(ds[-1] + 1.0)


# ---------------------------------------------------------------------------
# Batched beam == dense (exact) at full beam width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("distance", KERNEL_DISTANCES)
def test_full_beam_bit_identical_to_dense(distance):
    """beam >= level size must reproduce search_dense *bit-identically* on
    every kernelised form: the rowwise (gathered) kernel arithmetic matches
    the pairwise kernel element-for-element, and the candidate sets
    coincide, so dists, ids and the candidate counts are equal arrays."""
    data, idx = _build(distance)
    dist = dl.get(distance)
    r = float(rl.estimate_radius(jnp.asarray(data), dist, quantile=0.6))
    mc = msa.max_children(idx)
    Q = jnp.asarray(data[:12])
    dense = nsa.search_dense(idx, Q, dist=dist, k=7, r=r)
    beam = nsa.search_beam(idx, Q, dist=dist, k=7, r=r, beam=10_000,
                           max_children=mc)
    np.testing.assert_array_equal(np.asarray(dense.dists),
                                  np.asarray(beam.dists))
    np.testing.assert_array_equal(np.asarray(dense.ids), np.asarray(beam.ids))
    np.testing.assert_array_equal(np.asarray(dense.n_candidates),
                                  np.asarray(beam.n_candidates))


@pytest.mark.parametrize("distance", ["euclidean", "cosine"])
def test_full_beam_bit_identical_with_leaf_filter(distance):
    data, idx = _build(distance, seed=5)
    dist = dl.get(distance)
    r = float(rl.estimate_radius(jnp.asarray(data), dist, quantile=0.4))
    mc = msa.max_children(idx)
    Q = jnp.asarray(data[:8])
    dense = nsa.search_dense(idx, Q, dist=dist, k=5, r=r,
                             leaf_radius_filter=True)
    beam = nsa.search_beam(idx, Q, dist=dist, k=5, r=r, beam=10_000,
                           max_children=mc, leaf_radius_filter=True)
    np.testing.assert_array_equal(np.asarray(dense.dists),
                                  np.asarray(beam.dists))
    np.testing.assert_array_equal(np.asarray(dense.ids), np.asarray(beam.ids))


def test_full_beam_matches_dense_nonkernel_form():
    """Forms without a kernel (jaccard) fall back to the registry inside
    rank_candidates; full-width beam must still return the dense id set."""
    rng = np.random.default_rng(7)
    data = np.abs(rng.normal(size=(200, 4)).astype(np.float32))
    idx, _ = msa.build_index(data, gl=25, distance="jaccard",
                             key=jax.random.PRNGKey(7))
    dist = dl.get("jaccard")
    r = float(rl.estimate_radius(jnp.asarray(data), dist, quantile=0.7))
    mc = msa.max_children(idx)
    Q = jnp.asarray(data[:6])
    dense = nsa.search_dense(idx, Q, dist=dist, k=5, r=r)
    beam = nsa.search_beam(idx, Q, dist=dist, k=5, r=r, beam=10_000,
                           max_children=mc)
    for i in range(6):
        assert (set(np.asarray(beam.ids[i]).tolist())
                == set(np.asarray(dense.ids[i]).tolist()))


# ---------------------------------------------------------------------------
# Batched beam == legacy vmap beam (pruned widths)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("beam", [1, 4, 16])
def test_batched_beam_matches_vmap_beam(beam):
    """The kernel-layer batched beam and the seed per-query vmap beam visit
    the same candidates, so their result id sets coincide (distances agree
    to f32 tolerance — the two paths use different but equivalent
    arithmetic: rowwise Gram vs per-point subtraction)."""
    data, idx = _build("euclidean", n=400, d=8, seed=11)
    dist = dl.get("euclidean")
    mc = msa.max_children(idx)
    Q = jnp.asarray(data[:20])
    r = _gap_radius(idx, "euclidean", Q)
    new = nsa.search_beam(idx, Q, dist=dist, k=5, r=r, beam=beam,
                          max_children=mc)
    old = nsa.search_beam_vmap(idx, Q, dist=dist, k=5, r=r, beam=beam,
                               max_children=mc)
    np.testing.assert_allclose(np.asarray(new.dists), np.asarray(old.dists),
                               rtol=1e-3, atol=3e-3)
    np.testing.assert_array_equal(np.asarray(new.n_candidates),
                                  np.asarray(old.n_candidates))
    for i in range(20):
        assert (set(np.asarray(new.ids[i]).tolist())
                == set(np.asarray(old.ids[i]).tolist())), i


def test_single_query_squeeze():
    data, idx = _build("euclidean", seed=13)
    dist = dl.get("euclidean")
    r = float(rl.estimate_radius(jnp.asarray(data), dist, quantile=0.5))
    mc = msa.max_children(idx)
    res = nsa.search_beam(idx, jnp.asarray(data[0]), dist=dist, k=3, r=r,
                          beam=8, max_children=mc)
    assert res.dists.shape == (3,) and res.ids.shape == (3,)
    assert int(res.ids[0]) == 0  # finds itself


# ---------------------------------------------------------------------------
# Fused rank kernel: interpret-mode Pallas vs reference oracle
# ---------------------------------------------------------------------------

RANK_SHAPES = [(3, 17, 5, 4), (9, 130, 12, 7), (1, 300, 2, 1), (16, 64, 24, 9)]


@pytest.mark.parametrize("form", kref.FORMS)
@pytest.mark.parametrize("b,w,d,k", RANK_SHAPES)
def test_rank_kernel_interpret_parity(form, b, w, d, k):
    rng = np.random.default_rng(b * 100 + w)
    Q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(b, w, d)).astype(np.float32))
    ok = jnp.asarray(rng.random((b, w)) > 0.3)
    gd, gi = ops.rank_candidates(Q, C, ok, form, k=k, force_pallas=True,
                                 bq=4, bn=32)
    wd, wi = kref.rank_ref(Q, C, ok, k, form)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-4,
                               atol=1e-4)
    # id sets equal modulo ties among equal (incl. masked BIG) distances
    gd_, wd_ = np.asarray(gd), np.asarray(wd)
    for i in range(b):
        real = gd_[i] < kref.BIG / 2
        assert (set(np.asarray(gi[i])[real].tolist())
                == set(np.asarray(wi[i])[real].tolist()))


def test_rank_kernel_all_masked():
    Q = jnp.zeros((2, 4), jnp.float32)
    C = jnp.zeros((2, 10, 4), jnp.float32)
    ok = jnp.zeros((2, 10), bool)
    gd, gi = ops.rank_candidates(Q, C, ok, "l2", k=3, force_pallas=True,
                                 bq=2, bn=8)
    assert (np.asarray(gd) > kref.BIG / 2).all()


def test_rank_padding_never_selected():
    """Candidate-axis padding (w not a bn multiple) ranks as BIG."""
    rng = np.random.default_rng(5)
    Q = jnp.asarray(rng.normal(size=(3, 6)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(3, 13, 6)).astype(np.float32))
    ok = jnp.ones((3, 13), bool)
    gd, gi = ops.rank_candidates(Q, C, ok, "l2", k=13, force_pallas=True,
                                 bq=2, bn=8)
    assert ((np.asarray(gi) >= 0) & (np.asarray(gi) < 13)).all()


def test_search_end_to_end_force_pallas():
    """Both search modes run the Pallas kernel bodies (interpret) end to end
    and agree with the reference dispatch."""
    data, idx = _build("euclidean", n=200, d=8, seed=17)
    dist = dl.get("euclidean")
    mc = msa.max_children(idx)
    Q = jnp.asarray(data[:6])
    r = _gap_radius(idx, "euclidean", Q, quantile=0.5)
    kc = ops.KernelConfig(bm=32, bn=32, bd=32, bq=4, force_pallas=True)
    for mode_kw in (dict(), dict(leaf_radius_filter=True)):
        d_ref = nsa.search_dense(idx, Q, dist=dist, k=5, r=r, **mode_kw)
        d_pl = nsa.search_dense(idx, Q, dist=dist, k=5, r=r, kernel=kc,
                                **mode_kw)
        np.testing.assert_allclose(np.asarray(d_pl.dists),
                                   np.asarray(d_ref.dists), rtol=1e-3,
                                   atol=3e-3)
        b_ref = nsa.search_beam(idx, Q, dist=dist, k=5, r=r, beam=16,
                                max_children=mc, **mode_kw)
        b_pl = nsa.search_beam(idx, Q, dist=dist, k=5, r=r, beam=16,
                               max_children=mc, kernel=kc, **mode_kw)
        np.testing.assert_allclose(np.asarray(b_pl.dists),
                                   np.asarray(b_ref.dists), rtol=1e-3,
                                   atol=3e-3)


# ---------------------------------------------------------------------------
# Child runs of a column-major table: ranked where the table lies
# ---------------------------------------------------------------------------

RUN_CASES = ["block_edges", "counts", "table_end", "tombstones", "radius"]


def _runs(case, n=300, d=20, b=5, r=4, mc=7, seed=23):
    """Queries, a table and child runs laid out as the beam descent lays
    them out (candidate ``j * mc + t`` is row ``starts[:, j] + t``)."""
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    starts = rng.integers(0, n - mc, size=(b, r))
    counts = rng.integers(1, mc + 1, size=(b, r))
    if case == "block_edges":  # runs across the 128- and 256-lane edges
        starts[:, :3] = [[125, 250, 121], [127, 128, 0], [252, 122, 6],
                         [124, 255, 249], [120, 256, 128]]
        counts[:, :3] = mc
    elif case == "counts":  # empty and full runs
        counts[:, 0], counts[:, 1] = 0, mc
    elif case == "table_end":  # runs in the last, partial block
        starts[:, 0], counts[:, 0] = n - mc, mc
        starts[:, 1], counts[:, 1] = n - 3, 3
    starts = jnp.asarray(starts.astype(np.int32))
    t = jnp.arange(mc)
    idx = jnp.clip(starts[:, :, None] + t, 0, n - 1).reshape(b, r * mc)
    ok = (t < jnp.asarray(counts)[:, :, None]).reshape(b, r * mc)
    live = None
    if case == "tombstones":
        live = jnp.asarray(rng.random(n) > 0.3)
    return Q, pts, starts, idx, ok, live


def _assert_same_ranking(got, want):
    """Same slots (ids) in the same order; dists equal to float32 rounding
    (the in-place kernel sums the features in another order)."""
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-6, atol=0)
    for a, b_ in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("case", RUN_CASES)
@pytest.mark.parametrize("form", ["l2", "cosine"])
def test_runs_ranked_in_place_match_row_gather(form, case, monkeypatch):
    """The in-place path (``rank_runs_pallas``: lane blocks of ``points.T``
    reduced against each query, ranked in the same kernel) ranks child runs
    as the row gather + ``rank_pallas`` does; through a whole beam search
    too, with a radius that cuts the beams."""
    kc = ops.KernelConfig(bq=4, bn=32, force_pallas=True)
    if case == "radius":
        distance = "euclidean" if form == "l2" else "cosine"
        data, idx = _build(distance, n=300, d=8, seed=29)
        dist = dl.get(distance)
        Q = jnp.asarray(data[:9])
        r = _gap_radius(idx, distance, Q, quantile=0.2)
        run = lambda: nsa.search_beam(idx, Q, dist=dist, k=5, r=r, beam=4,
                                      max_children=msa.max_children(idx),
                                      kernel=kc)
        want = run()
        monkeypatch.setattr(ops, "column_major", lambda table: True)
        jax.clear_caches()  # the layout is read when search_beam traces
        got = run()
        n_full = np.asarray(want.n_candidates)
        assert n_full.min() < n_full.max()  # the radius cut some beams
        _assert_same_ranking(got, want)
        return
    Q, pts, starts, idx, ok, live = _runs(case)
    sq = jnp.sum(pts * pts, axis=1)
    rank = lambda: ops.rank_gathered(Q, pts, sq, idx, ok, form, k=6,
                                     slot_valid=live, run_starts=starts,
                                     config=kc)
    want = rank()
    monkeypatch.setattr(ops, "column_major", lambda table: True)
    _assert_same_ranking(rank(), want)


# ---------------------------------------------------------------------------
# Memory honesty: the dense path builds no [B, n, d] broadcast cube
# ---------------------------------------------------------------------------


def test_dense_l1_never_materialises_cube():
    """With row_chunk streaming, no intermediate of the traced dense search
    reaches [B, n_leaf, d] elements for a broadcast (l1) distance."""
    data, idx = _build("manhattan", n=512, d=16, gl=64, seed=19)
    dist = dl.get("manhattan")
    B, n0, d = 8, idx.levels[0].points.shape[0], 16
    kc = ops.KernelConfig(row_chunk=64)
    closed = jax.make_jaxpr(
        lambda q: nsa.search_dense(idx, q, dist=dist, k=5, r=2.0, kernel=kc)
    )(jnp.zeros((B, d), jnp.float32))

    cube = B * n0 * d
    seen = max_outvar_elems(closed.jaxpr)
    assert seen < cube, (seen, cube)
