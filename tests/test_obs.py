"""Unified telemetry layer (DESIGN.md §3.11): metric name lint, registry
thread-safety, histogram percentile fidelity, exporter formats,
deterministic trace sampling, span-tree integrity through a real two_stage
query behind the router, and the instrumentation overhead guard."""

import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import names as mnames
from repro.obs.metrics import (
    MetricsRegistry,
    to_json,
    to_prometheus,
)
from repro.core.index import PDASCIndex
from repro.query import Query
from repro.serving import BatchingEngine, ReplicaSet, Router, RouterConfig


# --------------------------- name catalogue lint -----------------------------


def test_every_catalogue_name_matches_the_convention():
    """The single source of truth (obs/names.py) must itself be clean:
    every documented name parses as subsystem_name_unit with a known
    subsystem and unit, and carries a valid kind + help string."""
    assert len(mnames.CATALOGUE) >= 25
    for name, (kind, help_) in mnames.CATALOGUE.items():
        m = mnames.NAME_RE.match(name)
        assert m is not None, name
        assert m.group("subsystem") in mnames.SUBSYSTEMS
        assert m.group("unit") in mnames.UNITS
        assert kind in ("counter", "gauge", "histogram"), name
        assert help_, f"{name} has no help text"
        assert mnames.subsystem(name) == m.group("subsystem")


def test_strict_registry_rejects_undocumented_and_malformed_names():
    reg = MetricsRegistry(strict=True)
    with pytest.raises(ValueError, match="catalogue"):
        reg.counter("engine_made_up_total")
    with pytest.raises(ValueError, match="convention"):
        reg.counter("Bad-Name")
    with pytest.raises(ValueError, match="documented as a"):
        reg.gauge(mnames.ENGINE_REQUESTS)  # documented as a counter
    # non-strict: regex-checked only
    loose = MetricsRegistry(strict=False)
    loose.counter("engine_made_up_total").inc()
    with pytest.raises(ValueError, match="convention"):
        loose.counter("made_up")
    with pytest.raises(ValueError, match="already registered"):
        loose.gauge("engine_made_up_total")


# --------------------------- registry thread-safety --------------------------


def test_concurrent_writers_lose_no_updates():
    """8 threads hammer one counter, per-thread labelled counters, and one
    histogram; every update must land (per-series locks, no torn sums)."""
    reg = MetricsRegistry(strict=False)
    n_threads, per = 8, 2000
    barrier = threading.Barrier(n_threads)

    def worker(w):
        shared = reg.counter("engine_shared_total")
        mine = reg.counter("engine_mine_total", worker=str(w))
        hist = reg.histogram("engine_lat_seconds")
        barrier.wait()
        for i in range(per):
            shared.inc()
            mine.inc(2.0)
            hist.observe(1e-4 * (i + 1))

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["engine_shared_total"]["series"][0]["value"] == \
        n_threads * per
    assert all(row["value"] == 2.0 * per
               for row in snap["engine_mine_total"]["series"])
    h = snap["engine_lat_seconds"]["series"][0]["hist"]
    assert h["count"] == n_threads * per
    assert sum(h["counts"]) == n_threads * per
    assert h["sum"] == pytest.approx(
        n_threads * sum(1e-4 * (i + 1) for i in range(per)), rel=1e-9)


def test_disabled_registry_is_a_no_op():
    reg = MetricsRegistry(strict=False)
    c = reg.counter("engine_x_total")
    h = reg.histogram("engine_x_seconds")
    reg.enabled = False
    c.inc()
    h.observe(1.0)
    reg.enabled = True
    c.inc()
    assert c.snapshot() == 1.0
    assert h.snapshot()["count"] == 0


# --------------------------- histogram fidelity ------------------------------


def test_histogram_percentiles_track_numpy_within_one_bucket():
    """Fixed factor-2 log buckets: the interpolated percentile may be off
    by at most one bucket width, i.e. within a factor of 2 of numpy's
    exact answer (and in practice much closer)."""
    rng = np.random.default_rng(3)
    samples = rng.lognormal(mean=-6.0, sigma=1.0, size=20_000)
    reg = MetricsRegistry(strict=False)
    h = reg.histogram("engine_t_seconds")
    for v in samples:
        h.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        exact = float(np.percentile(samples, q * 100))
        est = h.percentile(q)
        assert exact / 2 <= est <= exact * 2, (q, exact, est)
    # the estimate is clamped to the really-seen range
    assert samples.min() <= h.percentile(0.0) <= h.percentile(1.0)
    assert h.percentile(1.0) == pytest.approx(samples.max())


def test_histogram_bucket_boundaries_are_le_semantics():
    """An observation exactly on a bound lands in that bound's bucket
    (Prometheus `le` semantics), and export cumulates correctly."""
    reg = MetricsRegistry(strict=False)
    h = reg.histogram("engine_b_seconds", bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 2.0, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [2, 1, 1, 1]  # le=1: {0.5, 1.0}; +Inf: {100.0}
    text = to_prometheus(reg.snapshot())
    assert 'engine_b_seconds_bucket{le="1"} 2' in text
    assert 'engine_b_seconds_bucket{le="2"} 3' in text
    assert 'engine_b_seconds_bucket{le="4"} 4' in text
    assert 'engine_b_seconds_bucket{le="+Inf"} 5' in text
    assert "engine_b_seconds_count 5" in text


# --------------------------- exporters ---------------------------------------


def test_snapshot_exports_in_both_formats():
    reg = MetricsRegistry(strict=False)
    reg.counter("engine_req_total", engine="r0").inc(3)
    reg.gauge("engine_depth_count").set(7)
    reg.histogram("engine_wait_seconds").observe(0.5)
    snap = reg.snapshot()
    # JSON: round-trips to the same plain dict
    assert json.loads(to_json(snap)) == json.loads(json.dumps(snap))
    text = to_prometheus(snap)
    assert '# TYPE engine_req_total counter' in text
    assert 'engine_req_total{engine="r0"} 3' in text
    assert '# TYPE engine_depth_count gauge' in text
    assert 'engine_depth_count 7' in text
    assert '# TYPE engine_wait_seconds histogram' in text
    assert 'engine_wait_seconds_sum 0.5' in text


def test_prometheus_label_values_are_escaped_and_reparse():
    """Exporter hardening: backslashes, quotes and newlines in label
    values must escape per the Prometheus text format — a scraper parsing
    the line back recovers the original value exactly."""
    hostile = {
        "back\\slash": 'v1"quoted"',
        "multi\nline": "tab\tok",
        "plain": 'a\\b"c\nd',
    }
    reg = MetricsRegistry(strict=False)
    for i, (k, v) in enumerate(hostile.items()):
        reg.counter("engine_esc_total", label=k + v).inc(i + 1)
    text = to_prometheus(reg.snapshot())
    assert "\n" == text[-1] or "\n" in text
    # every sample line must be single-line and round-trip-parseable
    import re

    seen = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        m = re.fullmatch(
            r'(?P<name>\w+)(\{label="(?P<val>(?:[^"\\]|\\.)*)"\})? '
            r'(?P<value>\S+)', line)
        assert m is not None, f"unparseable exposition line: {line!r}"
        if m.group("val") is not None:
            unescaped = (m.group("val")
                         .replace("\\n", "\n")
                         .replace('\\"', '"')
                         .replace("\\\\", "\\"))
            seen[unescaped] = float(m.group("value"))
    assert seen == {k + v: float(i + 1)
                    for i, (k, v) in enumerate(hostile.items())}
    # HELP text with newlines/backslashes must stay single-line too
    snap = reg.snapshot()
    snap["engine_esc_total"]["help"] = "line1\nline2 \\ slash"
    text2 = to_prometheus(snap)
    for line in text2.splitlines():
        if line.startswith("# HELP"):
            assert "line1\\nline2 \\\\ slash" in line


def test_histogram_ignores_non_finite_observations():
    """A NaN/inf observation must not poison the sum/min/max (one bad
    latency sample would otherwise wreck every later percentile)."""
    reg = MetricsRegistry(strict=False)
    h = reg.histogram("engine_nf_seconds")
    h.observe(0.5)
    for bad in (float("nan"), float("inf"), float("-inf")):
        h.observe(bad)
    s = h.snapshot()
    assert s["count"] == 1 and s["sum"] == 0.5
    assert np.isfinite(s["min"]) and np.isfinite(s["max"])
    assert h.percentile(0.99) == pytest.approx(0.5)


def test_metrics_dumper_writes_snapshots(tmp_path):
    reg = MetricsRegistry(strict=False)
    reg.counter("engine_d_total").inc(5)
    path = tmp_path / "metrics.json"
    d = obs.MetricsDumper(reg, str(path), period_s=0)  # no thread
    d.dump()
    assert json.loads(path.read_text())["engine_d_total"]["series"][0][
        "value"] == 5
    prom = tmp_path / "metrics.prom"
    dp = obs.MetricsDumper(reg, str(prom), period_s=0)
    dp.close()  # close() always writes a final snapshot
    assert "engine_d_total 5" in prom.read_text()


# --------------------------- trace sampling ----------------------------------


def test_trace_sampling_is_deterministic_by_seq():
    buf = obs.TraceBuffer(maxlen=8)
    sampler = obs.TraceSampler(4, buffer=buf)
    picked = [seq for seq in range(20)
              if sampler.sample("request", seq) is not None]
    assert picked == [0, 4, 8, 12, 16]
    # same workload, fresh sampler -> the same picks, always
    again = obs.TraceSampler(4)
    assert picked == [s for s in range(20) if again.should_sample(s)]
    assert obs.TraceSampler(0).sample("request", 0) is None


def test_trace_buffer_bounds_and_exemplar_selection():
    buf = obs.TraceBuffer(maxlen=4)
    for seq in range(8):
        tr = obs.Trace("request", seq=seq, buffer=buf)
        tr.root.t1 = tr.root.t0 + 0.01 * (seq + 1)  # synthetic duration
        tr.finish()
    kept = buf.traces()
    assert len(kept) == 4 and [t.seq for t in kept] == [4, 5, 6, 7]
    assert buf.exemplar().seq == 7  # no target -> slowest
    assert buf.exemplar(0.05).seq == 4  # closest to 50 ms


def test_span_mirroring_and_nesting():
    """span() mirrors one child into every active parent and nests."""
    t1, t2 = obs.Trace("a"), obs.Trace("b")
    with obs.activate([t1.root, t2.root]):
        assert obs.is_tracing()
        with obs.span("stage", n=1):
            with obs.span("inner"):
                pass
    assert not obs.is_tracing()
    for tr in (t1, t2):
        (stage,) = tr.root.children
        assert stage.name == "stage" and stage.attrs == {"n": 1}
        (inner,) = stage.children
        assert inner.name == "inner" and inner.t1 is not None
    # inactive: only the profiler annotation; no span tree is touched
    from jax.profiler import TraceAnnotation

    bare = obs.span("whatever")
    assert isinstance(bare, TraceAnnotation)
    with bare:
        assert not obs.is_tracing()
    for tr in (t1, t2):
        assert [c.name for c in tr.root.children] == ["stage"]


# --------------------------- end-to-end span tree ----------------------------


@pytest.fixture(scope="module")
def store_tier():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(500, 16)).astype(np.float32)
    idx = PDASCIndex.build(X, gl=64, distance="euclidean", store="int8",
                           store_block=64)
    idx.release_dense_payload()
    query = Query(k=5, execution="two_stage", beam=16, rerank_width=16,
                  with_stats=False)
    rs = ReplicaSet(idx, query, n_replicas=1, batch_size=4, max_wait_ms=0.5)
    router = Router(rs, RouterConfig(deadline_s=30.0, seed=0, trace_every=1))
    router.search(X[0])  # warmup compile (also traced — that is fine)
    yield rs, router, X
    router.close(close_replicas=True)


def test_two_stage_span_tree_integrity(store_tier):
    """One traced two_stage query through the Router yields the full span
    tree — queue -> dispatch -> batch -> scan -> rerank -> granule fetch —
    with parent/child time containment and self-times partitioning the
    request wall clock (within 10%)."""
    _, router, X = store_tier
    t0 = time.perf_counter()
    router.search(X[7])
    wall = time.perf_counter() - t0
    tr = router.traces.traces()[-1]
    spans = list(tr.root.walk())
    names = [s.name for s in spans]
    for expect in ("request", "attempt", "queue_wait", "batch_wait",
                   "execute", "plan", "descend", "scan", "rerank",
                   "granule_fetch"):
        assert expect in names, (expect, names)
    # every span closed, every child inside its parent's window
    eps = 2e-3
    for s in spans:
        assert s.t1 is not None, s.name
        for c in s.children:
            assert c.t0 >= s.t0 - eps and c.t1 <= s.t1 + eps, (
                s.name, c.name)
    # self-times partition the root: they telescope to the root duration,
    # and the root tracks the externally measured wall clock within 10%
    assert sum(s.self_time for s in spans) == pytest.approx(
        tr.root.duration, rel=1e-6)
    assert tr.root.duration == pytest.approx(wall, rel=0.10)
    # the device stages carry their attribution attrs
    scan = next(s for s in spans if s.name == "scan")
    assert scan.attrs["kind"] == "device" and scan.attrs["backend"] == "int8"
    fetch = next(s for s in spans if s.name == "granule_fetch")
    assert fetch.attrs["kind"] == "host" and fetch.attrs["granules"] >= 1
    # render: one line per span, millisecond-scaled
    text = tr.render()
    assert text.count("\n") == len(spans)
    assert "granule_fetch" in text and "ms" in text


def test_untraced_requests_record_no_spans(store_tier):
    _, router, X = store_tier
    before = len(router.traces)
    every_n, router._sampler.every_n = router._sampler.every_n, 0
    try:
        router.search(X[3])
    finally:
        router._sampler.every_n = every_n
    assert len(router.traces) == before


def test_engine_stats_snapshot_is_atomic_and_isolated():
    """Satellite: the deprecated ``engine.stats`` view is a consistent
    copy taken under the stats lock — mutating it never corrupts the
    engine, and concurrent reads see internally consistent values."""
    eng = BatchingEngine(lambda b, n: b, batch_size=2, max_wait_ms=0.5,
                         pad_payload=np.zeros(3, np.float32))
    try:
        for i in range(8):
            eng.submit(np.full(3, float(i), np.float32)).wait(timeout=10)
        snap = eng.stats
        snap["requests"] = -999  # a copy: the engine must not notice
        assert eng.stats["requests"] == 8
        assert eng.stats is not eng.stats  # fresh copy per read
    finally:
        eng.close()


# --------------------------- overhead guard ----------------------------------


def test_instrumented_engine_throughput_overhead_is_bounded():
    """Instrumented throughput >= 0.95x uninstrumented. The handler is
    compute-dominated (~2 ms per batch, like a real jitted search), so the
    per-batch instrumentation cost (a few lock+add counter bumps) must
    disappear into it. Best-of-3 alternating trials absorb scheduler
    noise."""

    def handler(batch, n_valid):
        time.sleep(0.002)
        return batch

    def throughput() -> float:
        eng = BatchingEngine(handler, batch_size=4, max_wait_ms=0.2,
                             pad_payload=np.zeros(3, np.float32))
        try:
            eng.submit(np.zeros(3, np.float32)).wait(timeout=10)  # warm
            n = 100
            t0 = time.perf_counter()
            reqs = [eng.submit(np.full(3, float(i), np.float32))
                    for i in range(n)]
            for r in reqs:
                r.wait(timeout=30)
            return n / (time.perf_counter() - t0)
        finally:
            eng.close()

    off, on = [], []
    try:
        for _ in range(3):
            obs.set_enabled(False)
            off.append(throughput())
            obs.set_enabled(True)
            on.append(throughput())
    finally:
        obs.set_enabled(True)
    ratio = max(on) / max(off)
    assert ratio >= 0.95, (off, on)
