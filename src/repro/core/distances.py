"""Distance-function registry for PDASC.

The paper's central flexibility claim is that the index builder (MSA) and the
searcher (NSA) are parameterised by an *arbitrary* dissimilarity function: any
``delta: X x X -> R`` that is non-negative, symmetric and zero on identical
points (a metric is *not* required — k-medoids only consumes pairwise
dissimilarities).

Every distance here is exposed in two forms:

* ``point(x, y)``     — single-pair dissimilarity, ``[d] x [d] -> scalar``.
* ``pairwise(X, Y)``  — batched cross matrix, ``[m, d] x [n, d] -> [m, n]``.

All functions are pure ``jnp`` (jit / vmap / grad safe).  ``pairwise`` for the
Gram-form distances (l2 / cosine / dot) is written as a matmul so that XLA maps
it onto the MXU; the Pallas kernels in ``repro.kernels`` implement the same
contracts with explicit VMEM tiling for the TPU hot path and are validated
against these references.

Registry entries carry structural traits used elsewhere:

* ``gram_form``   — pairwise distance reducible to a Gram matrix (MXU-friendly).
* ``is_metric``   — satisfies the triangle inequality (p>=1 Minkowski,
  Haversine). PDASC does *not* rely on this — it is metadata used by tests and
  by baselines that do require a metric (e.g. KD-tree-style pruning).
* ``needs_dim``   — fixed input dimensionality (Haversine: d == 2).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-12
# Gram-form dots run at full f32 precision (same choice as
# ``repro.kernels.ref.PRECISION``): a TPU f32 dot at default precision is a
# single bf16 pass, and xx + yy - 2g amplifies that error past the gaps
# between near neighbours.
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Point-wise definitions
# ---------------------------------------------------------------------------


def _minkowski_point(x: Array, y: Array, p: float) -> Array:
    diff = jnp.abs(x - y)
    if p == jnp.inf:
        return jnp.max(diff, axis=-1)
    if p == 1.0:
        return jnp.sum(diff, axis=-1)
    if p == 2.0:
        return jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0))
    # Generic (includes fractional p < 1 — not a metric, but PDASC supports it;
    # the paper cites Aggarwal et al. on fractional distances improving
    # clustering in high dimension).
    return jnp.power(jnp.sum(jnp.power(diff, p), axis=-1), 1.0 / p)


def _cosine_point(x: Array, y: Array) -> Array:
    xn = jnp.sqrt(jnp.maximum(jnp.sum(x * x, axis=-1), _EPS))
    yn = jnp.sqrt(jnp.maximum(jnp.sum(y * y, axis=-1), _EPS))
    cos = jnp.sum(x * y, axis=-1) / (xn * yn)
    return 1.0 - jnp.clip(cos, -1.0, 1.0)


def _haversine_point(x: Array, y: Array) -> Array:
    # x, y: [..., 2] = (lat, lon) in radians.  Unit-sphere great-circle angle;
    # multiply by the sphere radius externally if a length is needed (the paper
    # uses the raw value — their Municipalities radii are in these units).
    lat1, lon1 = x[..., 0], x[..., 1]
    lat2, lon2 = y[..., 0], y[..., 1]
    dlat = lat2 - lat1
    dlon = lon2 - lon1
    a = (
        jnp.sin(dlat / 2.0) ** 2
        + jnp.cos(lat1) * jnp.cos(lat2) * jnp.sin(dlon / 2.0) ** 2
    )
    return 2.0 * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


def _jaccard_point(x: Array, y: Array) -> Array:
    # Weighted (Ruzicka) Jaccard for non-negative vectors; reduces to the set
    # Jaccard distance on binary data.  The paper lists Jaccard as future work;
    # k-medoids accommodates it unchanged, so we ship it.
    mn = jnp.sum(jnp.minimum(x, y), axis=-1)
    mx = jnp.sum(jnp.maximum(x, y), axis=-1)
    return 1.0 - mn / jnp.maximum(mx, _EPS)


def _dot_point(x: Array, y: Array) -> Array:
    # Negative inner product ("maximum inner product search" as a
    # dissimilarity). Not a metric and can be negative; PDASC only needs an
    # ordering, radii just shift.
    return -jnp.sum(x * y, axis=-1)


# ---------------------------------------------------------------------------
# Pairwise (cross-matrix) definitions
# ---------------------------------------------------------------------------


def _broadcast_pairwise(point_fn: Callable[[Array, Array], Array]):
    def pairwise(X: Array, Y: Array) -> Array:
        return point_fn(X[:, None, :], Y[None, :, :])

    return pairwise


def _sqeuclidean_gram(X: Array, Y: Array) -> Array:
    # ||x-y||^2 = ||x||^2 + ||y||^2 - 2 x.y — one [m,n] matmul on the MXU
    # instead of an [m,n,d] broadcast. Accumulates in f32 even for bf16
    # inputs (the cancellation in xx+yy-2g destroys ranking in bf16), and
    # clamps for the residual cancellation.
    xx = jnp.sum(X.astype(jnp.float32) ** 2, axis=-1)
    yy = jnp.sum(Y.astype(jnp.float32) ** 2, axis=-1)
    g = jnp.einsum("md,nd->mn", X, Y, preferred_element_type=jnp.float32,
                   precision=_HIGHEST)
    return jnp.maximum(xx[:, None] + yy[None, :] - 2.0 * g, 0.0)


def _euclidean_pairwise(X: Array, Y: Array) -> Array:
    return jnp.sqrt(_sqeuclidean_gram(X, Y))


def _cosine_pairwise(X: Array, Y: Array) -> Array:
    xn = jnp.sqrt(jnp.maximum(jnp.sum(X.astype(jnp.float32) ** 2, axis=-1), _EPS))
    yn = jnp.sqrt(jnp.maximum(jnp.sum(Y.astype(jnp.float32) ** 2, axis=-1), _EPS))
    cos = jnp.einsum("md,nd->mn", X, Y, preferred_element_type=jnp.float32,
                     precision=_HIGHEST) / (xn[:, None] * yn[None, :])
    return 1.0 - jnp.clip(cos, -1.0, 1.0)


def _dot_pairwise(X: Array, Y: Array) -> Array:
    return -jnp.matmul(X, Y.T, precision=_HIGHEST)


def _minkowski_pairwise(p: float):
    def pairwise(X: Array, Y: Array) -> Array:
        if p == 2.0:
            return _euclidean_pairwise(X, Y)
        return _minkowski_point(X[:, None, :], Y[None, :, :], p)

    return pairwise


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Distance:
    """A registered dissimilarity function."""

    name: str
    point: Callable[[Array, Array], Array]
    pairwise: Callable[[Array, Array], Array]
    gram_form: bool = False
    is_metric: bool = True
    needs_dim: Optional[int] = None
    # Upper bound of the distance range if bounded (used by radius estimation
    # and by masking: masked slots get `big = 10 * bound` or 1e30).
    bound: Optional[float] = None

    def __call__(self, X: Array, Y: Array) -> Array:
        return self.pairwise(X, Y)


_REGISTRY: dict[str, Distance] = {}


def _state_eq(a, b) -> bool:
    """Equality for bound state (partial args, closure cells) that never
    lies towards True: captured callables compare structurally (re-imports
    recreate them), array-valued or failing comparisons count as unequal."""
    if a is b:
        return True
    if callable(a) and callable(b):
        return _fns_match(a, b)
    try:
        return bool(a == b)
    except Exception:
        return False


def _fns_match(f, g) -> bool:
    """Structural callable identity: same code location and the same bound
    state — ``functools.partial`` arguments AND closure cell values (two
    factory-made closures from the same source line differ exactly in what
    they captured; a captured *function* recurses structurally)."""
    fb = gb = ()
    if isinstance(f, functools.partial):
        fb = f.args + tuple(sorted(f.keywords.items()))
        f = f.func
    if isinstance(g, functools.partial):
        gb = g.args + tuple(sorted(g.keywords.items()))
        g = g.func

    def _loc(fn):
        code = getattr(fn, "__code__", None)
        where = (code.co_filename, code.co_firstlineno) if code else None
        return (getattr(fn, "__module__", None),
                getattr(fn, "__qualname__", None), where)

    if _loc(f) != _loc(g):
        return False
    fc = tuple(c.cell_contents for c in (getattr(f, "__closure__", None) or ()))
    gc = tuple(c.cell_contents for c in (getattr(g, "__closure__", None) or ()))
    state_f, state_g = fb + fc, gb + gc
    return len(state_f) == len(state_g) and all(
        _state_eq(x, y) for x, y in zip(state_f, state_g)
    )


def _same_entry(a: Distance, b: Distance) -> bool:
    """Structural identity for re-registration: same name, same traits, and
    the point/pairwise callables match structurally (:func:`_fns_match`).
    Function *objects* differ across module re-imports (fresh notebook
    kernels, pytest ``--forked``), so object equality is the wrong test."""
    return (
        a.name == b.name
        and (a.gram_form, a.is_metric, a.needs_dim, a.bound)
        == (b.gram_form, b.is_metric, b.needs_dim, b.bound)
        and _fns_match(a.point, b.point)
        and _fns_match(a.pairwise, b.pairwise)
    )


def register(dist: Distance, *, overwrite: bool = False) -> Distance:
    """Register ``dist`` under its name.

    Re-registering a structurally identical entry is a no-op (module
    re-import safe); a *different* entry under an existing name raises
    unless ``overwrite=True`` replaces it explicitly.
    """
    prev = _REGISTRY.get(dist.name)
    if prev is not None and not overwrite:
        if _same_entry(prev, dist):
            return prev
        raise ValueError(
            f"distance {dist.name!r} already registered with a different "
            f"definition; pass overwrite=True to replace it"
        )
    _REGISTRY[dist.name] = dist
    return dist


def get(name_or_dist) -> Distance:
    if isinstance(name_or_dist, Distance):
        return name_or_dist
    try:
        return _REGISTRY[name_or_dist]
    except KeyError:
        raise KeyError(
            f"unknown distance {name_or_dist!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def names() -> list[str]:
    return sorted(_REGISTRY)


register(
    Distance(
        name="manhattan",
        point=functools.partial(_minkowski_point, p=1.0),
        pairwise=_minkowski_pairwise(1.0),
    )
)
register(
    Distance(
        name="euclidean",
        point=functools.partial(_minkowski_point, p=2.0),
        pairwise=_euclidean_pairwise,
        gram_form=True,
    )
)
register(
    Distance(
        name="chebyshev",
        point=functools.partial(_minkowski_point, p=jnp.inf),
        pairwise=_minkowski_pairwise(jnp.inf),
    )
)
register(
    Distance(
        name="fractional05",
        point=functools.partial(_minkowski_point, p=0.5),
        pairwise=_minkowski_pairwise(0.5),
        is_metric=False,
    )
)
register(
    Distance(
        name="cosine",
        point=_cosine_point,
        pairwise=_cosine_pairwise,
        gram_form=True,
        is_metric=False,
        bound=2.0,
    )
)
register(
    Distance(
        name="haversine",
        point=_haversine_point,
        pairwise=_broadcast_pairwise(_haversine_point),
        needs_dim=2,
        bound=float(jnp.pi),
    )
)
register(
    Distance(
        name="jaccard",
        point=_jaccard_point,
        pairwise=_broadcast_pairwise(_jaccard_point),
        is_metric=False,
        bound=1.0,
    )
)
register(
    Distance(
        name="dot",
        point=_dot_point,
        pairwise=_dot_pairwise,
        gram_form=True,
        is_metric=False,
    )
)


def minkowski(p: float) -> Distance:
    """Ad-hoc (unregistered) Minkowski distance for arbitrary ``p``."""
    return Distance(
        name=f"minkowski_{p}",
        point=functools.partial(_minkowski_point, p=p),
        pairwise=_minkowski_pairwise(p),
        is_metric=p >= 1.0,
    )


# ---------------------------------------------------------------------------
# Chunked pairwise — bounded peak memory for the non-Gram distances
# ---------------------------------------------------------------------------


def pairwise_chunked(
    dist, X: Array, Y: Array, *, chunk: int = 4096
) -> Array:
    """``dist.pairwise`` computed in bounded-memory chunks.

    The broadcast form of the non-Gram distances materialises ``[m, n, d]``;
    chunking streams it as ``[chunk, n, d]`` slabs (many rows) or
    ``[m, chunk, d]`` slabs (few rows against a large ``Y`` — the search-path
    shape, where a small query batch meets a big level). Gram-form distances
    never materialise the cube and are dispatched directly.
    """
    dist = get(dist)
    m, n = X.shape[0], Y.shape[0]
    if dist.gram_form or (m <= chunk and n <= chunk):
        return dist.pairwise(X, Y)
    from repro.kernels.ref import stream_cols, stream_rows  # lazy: acyclic

    if m > chunk:
        return stream_rows(
            lambda xc, Yf: pairwise_chunked(dist, xc, Yf, chunk=chunk), X, Y, chunk
        )
    return stream_cols(dist.pairwise, X, Y, chunk)


BIG = 1e30  # sentinel for masked / invalid slots; larger than any real distance


def mask_invalid(D: Array, row_valid: Array | None, col_valid: Array | None) -> Array:
    """Replace distances involving invalid (padding) points with ``BIG``."""
    if row_valid is not None:
        D = jnp.where(row_valid[:, None], D, BIG)
    if col_valid is not None:
        D = jnp.where(col_valid[None, :], D, BIG)
    return D
