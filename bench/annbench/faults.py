"""Faults planted under the timed path, to show that ``correct`` catches them.

Each fault wraps the engine's handler (``cell.engine_for(fault=...)``), so
the window, the engine and the check run as in a sound run and only what
the handler hands back is broken:

``answer_altered``   the first id of every answer moved to the next row:
                     an answer altered where it is produced.
``half_batch``       the second half of each batch answered with the first
                     row's answer: half of the batch left out.
``descent_path``     the beam descent reads each query with its features
                     shifted by one place (a layout fault) and so goes down
                     the wrong path; the leaf candidates it reaches are then
                     ranked exactly, with the query's own vector. Ids are
                     well formed and every distance is true, so only recall
                     can see it.
``descent_path_half``  the same for the first half of each batch only.

    make("descent_path", cfg)(handler) -> handler
"""

from __future__ import annotations

import numpy as np

from annbench import spec


def _answer_altered(cfg):
    def wrap(handler):
        def broken(batch, n_valid):
            dists, ids = handler(batch, n_valid)
            ids = np.array(ids)
            ids[:, 0] = (ids[:, 0] + 1) % cfg["n"]
            return dists, ids
        return broken
    return wrap


def _half_batch(cfg):
    def wrap(handler):
        def broken(batch, n_valid):
            dists, ids = (np.array(a) for a in handler(batch, n_valid))
            half = max(n_valid // 2, 1)
            dists[half:], ids[half:] = dists[0], ids[0]
            return dists, ids
        return broken
    return wrap


def _descent_path(cfg, share: float = 1.0):
    import jax
    import jax.numpy as jnp

    direct = spec.distance(cfg["distance"]).direct
    k = cfg["k"]

    def wrap(handler):
        from repro.core import nsa

        idx, plan = handler.current, handler.plan()
        data = idx.data

        @jax.jit
        def search(Q, n_valid):
            moved = jnp.arange(Q.shape[0]) < jnp.maximum(
                jnp.round(share * n_valid), 1)
            path_q = jnp.where(moved[:, None], jnp.roll(Q, 1, axis=1), Q)
            cand, ok = nsa.descend_beam(
                data, path_q, dist=idx.distance, r=plan.radius,
                beam=handler.query.beam, max_children=idx.max_children)
            pts = jnp.take(data.levels[0].points, cand, axis=0)
            d = jnp.where(ok, direct(Q, pts), jnp.inf)
            neg, pos = jax.lax.top_k(-d, k)
            slots = jnp.take_along_axis(cand, pos, axis=1)
            return -neg, jnp.take(data.leaf_ids, slots)

        def broken(batch, n_valid):
            d, i = search(jnp.asarray(batch, jnp.float32), n_valid)
            return np.asarray(d), np.asarray(i)
        return broken
    return wrap


FAULTS = {
    "answer_altered": _answer_altered,
    "half_batch": _half_batch,
    "descent_path": _descent_path,
    "descent_path_half": lambda cfg: _descent_path(cfg, share=0.5),
}


def make(name: str, cfg: dict):
    """The handler wrapper that plants fault ``name`` in a cell of ``cfg``."""
    if name not in FAULTS:
        raise KeyError(f"unknown fault {name!r}; known: {sorted(FAULTS)}")
    return FAULTS[name](cfg)
