"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes on CPU; BlockSpecs are the TPU contract)."""

from _hypothesis_compat import hypothesis, st
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import FORMS, knn_ref, pairwise_ref

SHAPES = [(3, 5, 4), (17, 33, 7), (64, 64, 64), (130, 70, 129), (1, 300, 2)]
DTYPES = [np.float32, np.float16]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("m,n,d", SHAPES)
def test_pairwise_shape_sweep(form, m, n, d):
    rng = np.random.default_rng(m * 1000 + n)
    X = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    got = ops.pairwise_distance(X, Y, form, force_pallas=True, bm=32, bn=32,
                                bd=32)
    want = pairwise_ref(X, Y, form)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("form", ["l2", "cosine", "l1"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_dtype_sweep(form, dtype):
    rng = np.random.default_rng(11)
    X = jnp.asarray(rng.normal(size=(40, 19)).astype(dtype))
    Y = jnp.asarray(rng.normal(size=(50, 19)).astype(dtype))
    got = ops.pairwise_distance(X, Y, form, force_pallas=True, bm=16, bn=16,
                                bd=16)
    want = pairwise_ref(X.astype(jnp.float32), Y.astype(jnp.float32), form)
    tol = 5e-3 if dtype != np.float32 else 2e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_pairwise_bf16():
    rng = np.random.default_rng(12)
    X = jnp.asarray(rng.normal(size=(33, 20)), jnp.bfloat16)
    Y = jnp.asarray(rng.normal(size=(21, 20)), jnp.bfloat16)
    got = ops.pairwise_distance(X, Y, "l2", force_pallas=True, bm=16, bn=16,
                                bd=16)
    want = pairwise_ref(X.astype(jnp.float32), Y.astype(jnp.float32), "l2")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.05,
                               rtol=0.05)


@pytest.mark.parametrize("form", FORMS)
def test_knn_fused_vs_ref(form):
    rng = np.random.default_rng(13)
    Q = jnp.asarray(rng.normal(size=(37, 12)).astype(np.float32))
    DB = jnp.asarray(rng.normal(size=(301, 12)).astype(np.float32))
    gd, gi = ops.knn(Q, DB, form, k=9, force_pallas=True, bq=16, bn=64)
    wd, wi = knn_ref(Q, DB, 9, form)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-4,
                               atol=1e-4)
    for i in range(Q.shape[0]):  # id sets equal modulo ties
        assert set(np.asarray(gi[i]).tolist()) == set(np.asarray(wi[i]).tolist())


@hypothesis.given(
    m=st.integers(1, 40), n=st.integers(2, 80), d=st.integers(1, 24),
    k=st.integers(1, 8),
    form=st.sampled_from(["l2", "cosine", "l1", "dot"]),
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_knn_property_sweep(m, n, d, k, form):
    k = min(k, n)
    rng = np.random.default_rng(m * 77 + n)
    Q = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    DB = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    gd, gi = ops.knn(Q, DB, form, k=k, force_pallas=True, bq=8, bn=32)
    wd, _ = knn_ref(Q, DB, k, form)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-3,
                               atol=1e-3)
    # ascending + ids valid
    gd = np.asarray(gd)
    assert (np.diff(gd, axis=1) >= -1e-6).all()
    gi = np.asarray(gi)
    assert ((gi >= 0) & (gi < n)).all()


def test_padding_rows_never_returned():
    """DB padding (masked by n_valid) must not appear in results even when
    the padding would be the nearest point."""
    Q = jnp.zeros((4, 8), jnp.float32)
    DB = jnp.ones((10, 8), jnp.float32) * 5.0
    gd, gi = ops.knn(Q, DB, "l2", k=3, force_pallas=True, bq=4, bn=16)
    assert (np.asarray(gi) < 10).all()


def test_dispatch_fallback_nonkernel_distance():
    """haversine has no kernel form -> registry fallback still works."""
    rng = np.random.default_rng(14)
    X = jnp.asarray(rng.uniform(-1, 1, size=(6, 2)).astype(np.float32))
    D = ops.pairwise_distance(X, X, "haversine")
    assert np.asarray(D).shape == (6, 6)
    d_, i_ = ops.knn(X, X, "haversine", k=2)
    assert (np.asarray(i_)[:, 0] == np.arange(6)).all()


def test_resolve_form():
    from repro.core import distances as dl

    assert ops.resolve_form("euclidean") == "l2"
    assert ops.resolve_form(dl.get("manhattan")) == "l1"
    assert ops.resolve_form("sqeuclidean") == "sqeuclidean"
    assert ops.resolve_form("haversine") is None


@pytest.mark.parametrize("k,bn", [(1, 8), (4, 16), (10, 512), (16, 16)])
def test_merge_topk_matches_top_k_over_concat(k, bn):
    """The in-kernel extract-min merge equals ``lax.top_k`` over
    ``concat([state, tile])`` exactly — ids included, ties and the BIG /
    -1 initial state included (integer-valued distances force ties)."""
    import jax

    from repro.kernels.ref import BIG
    from repro.kernels.topk import merge_topk

    rng = np.random.default_rng(k * 100 + bn)
    bq, base = 8, 1000
    # running state: ascending, some slots still at the BIG / -1 init
    state_d = np.sort(rng.integers(0, 6, (bq, k)).astype(np.float32), axis=1)
    state_d[:, k // 2:] = BIG
    state_i = rng.integers(0, base, (bq, k)).astype(np.int32)
    state_i[:, k // 2:] = -1
    tile = rng.integers(0, 6, (bq, bn)).astype(np.float32)
    tile[:, ::3] = BIG  # masked candidates tie with the BIG state
    got_d, got_i = merge_topk(jnp.asarray(state_d), jnp.asarray(state_i),
                              jnp.asarray(tile), base)
    all_d = np.concatenate([state_d, tile], axis=1)
    all_i = np.concatenate(
        [state_i, np.broadcast_to(base + np.arange(bn), (bq, bn))], axis=1)
    neg, idx = jax.lax.top_k(-jnp.asarray(all_d), k)
    np.testing.assert_array_equal(np.asarray(got_d), -np.asarray(neg))
    np.testing.assert_array_equal(np.asarray(got_i),
                                  np.take_along_axis(all_i, np.asarray(idx), 1))
