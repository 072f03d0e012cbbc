"""The engine worker's stage spans, read back from a profiler trace.

A ``BatchingEngine`` over a small ``QueryHandler`` runs under
``jax.profiler`` on the CPU backend; the ``.xplane.pb`` it writes is read
with ``jax.profiler.ProfileData``. Every ``obs.span`` is a profiler
annotation, so the worker's six stages and the plan's own span show on the
worker thread's line of the host plane.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.index import PDASCIndex
from repro.query import Query
from repro.serving import BatchingEngine, QueryHandler

STAGES = ("engine.take_batch", "engine.stack", "engine.dispatch",
          "engine.device_wait", "engine.fetch", "engine.deliver")
BATCH = 4
UNSAMPLED = 3  # batches with no sampled request, then one fully sampled
D = 16


@jax.jit
def _slow(batch):
    """Returns ``batch`` unchanged after ~0.1 s of device work, so a sync
    anywhere in the handler would show as a long ``engine.dispatch``."""
    m = jnp.full((384, 384), 1.0 / 384, jnp.float32)
    acc = jax.lax.fori_loop(0, 200, lambda i, a: jnp.tanh(a @ m),
                            jnp.ones((384, 384), jnp.float32))
    return batch + 0.0 * acc[0, 0]


def _host_events(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(f"{plane.name}#{i}", e.name, e.start_ns, e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:CPU")
            for i, line in enumerate(plane.lines) for e in line.events]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(400, D)).astype(np.float32)
    idx = PDASCIndex.build(X, gl=64, distance="euclidean")
    qh = QueryHandler(idx, Query(k=5, execution="beam", beam=16,
                                 with_stats=False))

    def handler(batch, n_valid):
        return qh(_slow(batch), n_valid)

    def make_engine():
        return BatchingEngine(handler, batch_size=BATCH, max_wait_ms=60e3,
                              pad_payload=np.zeros(D, np.float32))

    queries = rng.normal(size=((UNSAMPLED + 1) * BATCH, D)).astype(
        np.float32)
    warm = make_engine()  # compile outside the trace
    for r in [warm.submit(q) for q in queries[:BATCH]]:
        r.wait(timeout=300)
    warm.close()

    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        # the worker starts inside the trace, so its first take_batch is
        # recorded; each batch departs only when full (max_wait is long)
        engine = make_engine()
        delivered, traces = [], []
        for b in range(UNSAMPLED + 1):
            reqs = []
            for q in queries[b * BATCH:(b + 1) * BATCH]:
                tr = obs.Trace("request") if b == UNSAMPLED else None
                traces.append(tr)
                reqs.append(engine.submit(q, span=tr.root if tr else None))
            delivered += [r.wait(timeout=300) for r in reqs]
        engine.close()
    finally:
        jax.profiler.stop_trace()
    expected = [jax.tree.map(np.asarray, qh(queries[b * BATCH:
                                                  (b + 1) * BATCH], BATCH))
                for b in range(UNSAMPLED + 1)]
    return dict(events=_host_events(log_dir), delivered=delivered,
                expected=expected, traces=[t for t in traces if t])


def _worker_spans(events, names):
    """(name, start, end) of ``names`` on the one line that holds them."""
    hits = [(ln, n, s, s + d) for ln, n, s, d in events if n in names]
    assert len({ln for ln, *_ in hits}) == 1, "spans on more than one line"
    return sorted(((n, s, e) for _, n, s, e in hits), key=lambda x: x[1])


def test_each_stage_span_opens_once_per_batch_in_order(traced):
    spans = _worker_spans(traced["events"], STAGES)
    batches = UNSAMPLED + 1
    # the last take_batch is the one close() ends with no batch
    assert [n for n, _, _ in spans] == list(STAGES) * batches + [STAGES[0]]
    assert all(e1 <= s2 for (_, _, e1), (_, s2, _) in zip(spans, spans[1:]))


def test_plan_span_sits_inside_dispatch(traced):
    spans = _worker_spans(traced["events"], {"engine.dispatch", "plan"})
    dispatch = [(s, e) for n, s, e in spans if n == "engine.dispatch"]
    plans = [(s, e) for n, s, e in spans if n == "plan"]
    assert len(plans) == len(dispatch) == UNSAMPLED + 1
    for (ps, pe), (ds, de) in zip(plans, dispatch):
        assert ds <= ps and pe <= de


def test_dispatch_returns_before_the_results_are_ready(traced):
    spans = _worker_spans(traced["events"],
                          {"engine.dispatch", "engine.device_wait"})
    dispatch = [e - s for n, s, e in spans if n == "engine.dispatch"]
    wait = [e - s for n, s, e in spans if n == "engine.device_wait"]
    # with no sampled request nothing in the handler syncs: the device's
    # ~0.1 s is waited for in engine.device_wait, not in engine.dispatch
    for d, w in list(zip(dispatch, wait))[:UNSAMPLED]:
        assert w > d and w > 10e6


def test_delivered_results_equal_the_handlers(traced):
    for b, (dists, ids) in enumerate(traced["expected"]):
        for i in range(BATCH):
            got_d, got_i = traced["delivered"][b * BATCH + i]
            assert isinstance(got_d, np.ndarray)
            np.testing.assert_array_equal(got_i, ids[i])
            np.testing.assert_allclose(got_d, dists[i], rtol=1e-6)


def test_sampled_span_trees_keep_their_shape(traced):
    assert len(traced["traces"]) == BATCH
    for tr in traced["traces"]:
        assert [c.name for c in tr.root.children] == [
            "queue_wait", "batch_wait", "execute"]
        (execute,) = tr.root.children[2:]
        assert [c.name for c in execute.children] == ["plan"]
