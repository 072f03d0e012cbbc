"""The blocked reference against a float64 NumPy brute force."""

import _paths  # noqa: F401
import numpy as np
import pytest

from annbench import cell as cell_lib, check, reference, spec


def _brute(X, Q, distance):
    X = X.astype(np.float64)
    Q = Q.astype(np.float64)
    if distance == "euclidean":
        return np.linalg.norm(Q[:, None, :] - X[None, :, :], axis=-1)
    xn = np.maximum(np.linalg.norm(X, axis=1), 1e-6)
    qn = np.maximum(np.linalg.norm(Q, axis=1), 1e-6)
    return 1.0 - np.clip((Q @ X.T) / (qn[:, None] * xn[None, :]), -1, 1)


@pytest.mark.parametrize("recipe,distance,d", [
    ("dense_embed", "euclidean", 100), ("tfidf_like", "cosine", 256)])
def test_neighbours_match_float64(recipe, distance, d):
    x = np.asarray(spec.recipe(recipe).generate(
        cell_lib.seed_key(2 ** 33 + 1), n_rows=3000 + 64, d=d))
    X, Q = x[:3000], x[3000:]
    db = reference.Database(X, spec.distance(distance), block=1024)
    ref_d, ref_i = db.neighbours(Q, 10)
    full = _brute(X, Q, distance)
    want = np.sort(full, axis=1)[:, :10]
    np.testing.assert_allclose(ref_d, want, rtol=1e-5, atol=1e-6)
    got = np.take_along_axis(full, ref_i, axis=1)
    assert (got <= want[:, -1:] * (1 + 1e-6) + 1e-12).all()
    assert len(np.unique(ref_i[0])) == 10


def test_direct_marks_ids_out_of_range():
    X = np.eye(4, dtype=np.float32)
    db = reference.Database(X, spec.distance("euclidean"), block=2)
    d = db.direct(X[:2], np.array([[0, 1], [-1, 9]]))
    np.testing.assert_allclose(d[0], [0.0, np.sqrt(2.0)], atol=1e-6)
    assert np.isnan(d[1]).all()


def test_seed_key_takes_seeds_beyond_32_bits():
    a = cell_lib.seed_key(5)
    b = cell_lib.seed_key(5 + 2 ** 32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        cell_lib.seed_key(-1)


def test_malformed_rows():
    ids = np.array([[0, 1, 2], [0, 0, 2], [0, 1, 5], [0, 1, 2]])
    d = np.array([[1.0, 2, 3], [1, 2, 3], [1, 2, 3], [1, 3, 2]])
    assert check.malformed_rows(ids, d, 5).tolist() == [False, True, True,
                                                       True]
