"""The declarative :class:`Query` spec — *what* to retrieve, never *how*.

A query names the result contract (``k``, ``radius``), the quality/cost
knobs (``beam`` schedule, ``rerank_width``, ``leaf_radius_filter``) and at
most a *preference* for the execution pipeline (``execution``, default
``"auto"``). Everything else — which pipeline actually runs, which kernel
ops it lowers onto, whether a tombstone mask or delta-scan leg folds into
the result — is decided by the planner (``repro.query.plan``) from the
index's capabilities at plan time.

Queries are frozen and hashable: a ``Query`` is a cache key. The plan cache
(``PDASCIndex.plan``) keys on ``(query, capability fingerprint)``, and the
jit caches underneath key on the query's static fields — two calls with an
equal ``Query`` hit the same compiled executable.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import numpy as np

from repro.core import distances as dist_lib
from repro.kernels import ops as kops

# Execution preferences a Query may name. "auto" lets the planner choose
# from the index's capabilities; the rest force a pipeline (and fail at plan
# time when the index cannot serve it). "beam_vmap" is the seed per-query
# baseline, kept for benchmarking.
EXECUTIONS = ("auto", "dense", "beam", "beam_vmap", "two_stage", "sharded")

Radius = Union[None, float, tuple]
Beam = Union[int, tuple]


def _freeze_schedule(value, *, numeric=float):
    """Normalise a scalar-or-per-level schedule to a hashable static value."""
    if value is None:
        return None
    if isinstance(value, (list, tuple)):
        return tuple(numeric(v) for v in value)
    return numeric(value)


@dataclasses.dataclass(frozen=True)
class Query:
    """Declarative k-ANN query spec (hashable; every field is jit-static).

    Attributes:
      k: neighbours to return.
      radius: search radius — scalar, per-level tuple indexed by level
        (``radius[0]`` = leaf, ``radius[-1]`` = top, matching
        ``nsa._per_level_radii``), or None for the index's
        ``default_radius`` (resolved at plan time).
      execution: pipeline preference, one of :data:`EXECUTIONS`. ``"auto"``
        picks from the index capabilities: ``two_stage`` once the dense
        payload was released, the batched ``beam`` hot path otherwise.
      beam: surviving prototypes per level — scalar or per-level schedule
        (same leaf-first level indexing as ``radius``).
      rerank_width: two-stage only — survivors of the quantised scan that
        advance to the exact rerank (None / <= 0 = ∞, bit-identical to
        ``beam``).
      exact_rerank: two-stage only — when False, skip stage 2 entirely and
        rank on quantised-scan distances alone (the graceful-degradation
        plan: cheapest possible serve, recall bounded by the code
        resolution). Ignored by pipelines with no rerank stage.
      leaf_radius_filter: apply the radius at the leaf ranking too (paper
        Algorithm 2 does not; this is the stricter variant).
      with_stats: include the candidate-count reduction (serving sets False).
      kernel: kernel-layer block knobs (None = defaults). With
        ``KernelConfig(auto=True)`` the planner resolves knobs left at their
        defaults from the persisted block-size tuner cache
        (``repro.kernels.autotune``) and re-plans — retracing the jitted
        pipelines — when the cached winners change; explicitly set fields
        still win.
    """

    k: int = 10
    radius: Radius = None
    execution: str = "auto"
    beam: Beam = 32
    rerank_width: Optional[int] = 128
    exact_rerank: bool = True
    leaf_radius_filter: bool = False
    with_stats: bool = True
    kernel: Optional[kops.KernelConfig] = None

    def __post_init__(self):
        if int(self.k) < 1:
            raise ValueError(f"query k must be >= 1, got {self.k}")
        object.__setattr__(self, "k", int(self.k))
        if self.execution not in EXECUTIONS:
            raise ValueError(
                f"unknown search mode {self.execution!r}; valid executions: "
                f"{EXECUTIONS}"
            )
        object.__setattr__(self, "radius", _freeze_schedule(self.radius))
        object.__setattr__(
            self, "beam", _freeze_schedule(self.beam, numeric=int)
        )
        if self.rerank_width is not None:
            object.__setattr__(self, "rerank_width", int(self.rerank_width))


def degraded(query: Query) -> Query:
    """The graceful-degradation rewrite of ``query`` (DESIGN.md §3.10).

    Under admission-control pressure the router serves this cheaper spec
    instead of rejecting: beam narrowed (halved, floor 8 per level), the
    exact rerank stage dropped (``exact_rerank=False`` — rank on quantised
    scan distances alone where the index stores codes; indices serving the
    exact payload just run the narrower beam), rerank width collapsed to
    ``k``, and stats off. Same ``k`` and radius — the result contract
    holds, only the quality/cost knobs move. Deterministic and frozen, so
    the degraded plan compiles once and caches like any other.
    """
    beam = query.beam
    if isinstance(beam, tuple):
        beam = tuple(max(8, b // 2) for b in beam)
    elif beam is not None:
        beam = max(8, int(beam) // 2)
    return dataclasses.replace(
        query,
        beam=beam,
        rerank_width=query.k,
        exact_rerank=False,
        with_stats=False,
    )


def is_concrete(Q) -> bool:
    """False inside a jit/shard_map trace (validation must be skipped there:
    a plan may be executed inside a lowered step, e.g. the dry-run cells)."""
    return not isinstance(Q, jax.core.Tracer)


def validate_query_batch(
    Q, dist: dist_lib.Distance, *, expect_dim: Optional[int] = None
) -> None:
    """Search-time query validation (the build/upsert counterpart of
    ``index._validate_points``): ``needs_dim`` distances reject wrong widths
    and non-finite rows fail loudly instead of silently poisoning every
    distance they touch. No-op on tracers (plans run inside jit too).

    Shape / dimensionality checks are metadata-only and always run. The
    non-finite data scan runs for *host* inputs only (numpy arrays, lists —
    what users and the serving engine's stacked batches pass): for an array
    already committed to a device it would force a blocking device->host
    transfer per call, stalling async dispatch on the serving hot path, so
    device arrays are trusted to have been validated when they were built.
    """
    if not is_concrete(Q):
        return
    import jax

    on_device = isinstance(Q, jax.Array)
    arr = None if on_device else np.asarray(Q)
    shape = Q.shape if on_device else arr.shape
    if len(shape) not in (1, 2):
        raise ValueError(f"queries must be [d] or [B, d], got shape {shape}")
    d = shape[-1]
    if dist.needs_dim is not None and d != dist.needs_dim:
        raise ValueError(
            f"distance {dist.name!r} needs d={dist.needs_dim} queries, got "
            f"d={d} at search time"
        )
    if expect_dim is not None and d != expect_dim:
        raise ValueError(
            f"query dimensionality d={d} does not match the index (d="
            f"{expect_dim})"
        )
    if arr is None:
        return
    finite = np.isfinite(np.asarray(arr, np.float32))
    if not finite.all():
        rows = finite.all(axis=-1)
        bad = int((~np.atleast_1d(rows)).sum())
        raise ValueError(
            f"queries contain non-finite values ({bad} rows with NaN/inf); "
            f"clean the queries before searching"
        )
