"""Quality & SLO observability (DESIGN.md §3.12): Wilson intervals,
shadow recall estimation against exhaustive recall on a seeded workload
(with degraded-leg attribution), multi-rate SLO burn alerts, and the
report/dashboard surface."""

import time

import numpy as np
import pytest

from repro import obs
from repro.obs import report as report_lib
from repro.obs.metrics import MetricsRegistry


# --------------------------- wilson interval ---------------------------------


def test_wilson_interval_properties():
    lo, hi = obs.wilson(95, 100)
    assert 0.88 < lo < 0.95 < hi < 1.0
    assert obs.wilson(0, 0) == (0.0, 1.0)  # no trials: trivially [0, 1]
    # degenerate proportions stay inside [0, 1] and keep width
    lo0, hi0 = obs.wilson(0, 20)
    loN, hiN = obs.wilson(20, 20)
    assert lo0 == 0.0 and hi0 > 0.05
    assert hiN == 1.0 and loN < 0.95
    # more trials -> tighter interval around the same proportion
    w_small = np.subtract(*reversed(obs.wilson(9, 10)))
    w_big = np.subtract(*reversed(obs.wilson(900, 1000)))
    assert w_big < w_small


# --------------------------- shadow recall estimation ------------------------


@pytest.fixture(scope="module")
def quality_index():
    from repro.core.index import PDASCIndex

    rng = np.random.default_rng(11)
    data = rng.normal(size=(500, 16)).astype(np.float32)
    queries = rng.normal(size=(64, 16)).astype(np.float32)
    idx = PDASCIndex.build(data, gl=32, distance="euclidean",
                           radius_quantile=0.35)
    return idx, data, queries


def test_online_estimate_matches_exhaustive_recall(quality_index):
    """Serve a seeded workload, shadow-sample 1-in-3: the estimator's
    online recall must sit within its own Wilson interval of the
    exhaustive (every-query) recall over the same served answers."""
    from repro.baselines.exact import exact_knn
    from repro.query import Query

    idx, data, queries = quality_index
    k = 5
    plan = idx.plan(Query(k=k, execution="beam", beam=8))
    served = [np.asarray(plan(q[None]).ids).reshape(-1) for q in queries]
    _, gt = exact_knn(queries, data, distance="euclidean", k=k)
    gt = np.asarray(gt)
    exhaustive = float(np.mean([
        len(set(int(x) for x in served[j] if x >= 0)
            & set(int(x) for x in gt[j])) / k
        for j in range(len(queries))
    ]))

    est = obs.RecallEstimator(idx, every_n=3)
    try:
        n_offered = sum(
            est.observe(j, queries[j], served[j], pipeline="beam")
            for j in range(len(queries)))
        assert n_offered == len([j for j in range(len(queries))
                                 if j % 3 == 0])
        assert est.drain(timeout=120)
        e = est.estimate()
        assert e["queries"] == n_offered
        assert e["trials"] == n_offered * k
        # the sampled estimate brackets the exhaustive recall
        assert e["wilson_lo"] <= exhaustive <= e["wilson_hi"], (e,
                                                                exhaustive)
        assert abs(e["recall"] - exhaustive) <= 0.15
        # the published series carry the (pipeline, leg) labels
        snap = obs.snapshot()
        rows = snap[obs.names.QUALITY_RECALL_MEAN]["series"]
        assert any(r["labels"] == {"pipeline": "beam", "leg": "normal"}
                   for r in rows)
    finally:
        est.close()


def test_degraded_leg_is_attributed_separately(quality_index):
    """A degraded serve (scan-only / halved beam) must land on its own
    (pipeline, leg) stats — a recall dip on the degraded leg is visible
    without polluting the normal leg's estimate."""
    from repro.query import Query, degraded

    idx, data, queries = quality_index
    k = 5
    q = Query(k=k, execution="beam", beam=8)
    plan_n = idx.plan(q)
    plan_d = idx.plan(degraded(q))
    est = obs.RecallEstimator(lambda: idx, every_n=1)  # callable source
    try:
        for j in range(10):
            est.observe(j, queries[j],
                        np.asarray(plan_n(queries[j][None]).ids)[0],
                        pipeline="beam", leg="normal")
        for j in range(10, 16):
            est.observe(j, queries[j],
                        np.asarray(plan_d(queries[j][None]).ids)[0],
                        pipeline="beam", leg="degraded")
        assert est.drain(timeout=120)
        assert est.legs() == [("beam", "degraded"), ("beam", "normal")]
        normal = est.estimate(leg="normal")
        deg = est.estimate(leg="degraded")
        assert normal["queries"] == 10 and deg["queries"] == 6
        # both legs answered something sane; the overall pool is the union
        both = est.estimate()
        assert both["queries"] == 16
        assert both["successes"] == normal["successes"] + deg["successes"]
        # reset_stats drops the estimate but keeps the worker alive
        est.reset_stats()
        assert est.estimate()["queries"] == 0
        est.observe(0, queries[0],
                    np.asarray(plan_n(queries[0][None]).ids)[0],
                    pipeline="beam")
        assert est.drain(timeout=120)
        assert est.estimate()["queries"] == 1
    finally:
        est.close()


def test_estimator_sampling_and_drop_accounting(quality_index):
    idx, data, queries = quality_index
    est = obs.RecallEstimator(idx, every_n=4, queue_max=1)
    try:
        assert [s for s in range(12) if est.should_sample(s)] == [0, 4, 8]
        est.every_n = 0  # disabled: observe becomes a no-op
        assert not est.observe(0, queries[0], np.arange(5))
    finally:
        est.close()


# --------------------------- SLO burn alerts ---------------------------------


def _latency_spec(**over):
    kw = dict(latency_p99_s=0.1, availability=None, window_s=1.0,
              fast_window_frac=0.5, min_samples=4, burn_threshold=2.0)
    kw.update(over)
    return obs.SLOSpec(**kw)


def test_slo_no_alert_when_clean():
    slo = obs.SLOTracker(_latency_spec())
    for _ in range(30):
        slo.record_request(0.01, ok=True)
    st = slo.evaluate()
    assert st["latency"]["sli"] == 1.0
    assert st["latency"]["burn_slow"] == 0.0
    assert not st["latency"]["alerting"]
    assert slo.alert_counts() == {} and slo.events() == []


def test_slo_burn_alert_fires_and_clears():
    slo = obs.SLOTracker(_latency_spec())
    for _ in range(10):
        slo.record_request(0.5, ok=True)  # all past the latency target
    st = slo.evaluate()
    assert st["latency"]["alerting"]
    assert slo.alert_counts() == {"latency": 1}
    # still burning: the alert edge does not re-fire
    slo.record_request(0.5, ok=True)
    slo.evaluate()
    assert slo.alert_counts() == {"latency": 1}
    # burn stops; once the bad samples age out of the window it clears
    time.sleep(1.1)
    for _ in range(10):
        slo.record_request(0.01, ok=True)
    st = slo.evaluate()
    assert not st["latency"]["alerting"]
    events = slo.events()
    assert [e["event"] for e in events] == ["burn_alert", "burn_clear"]
    assert events[0]["objective"] == "latency"
    # the alert counter series is published
    snap = obs.snapshot()
    assert any(r["labels"] == {"objective": "latency"} and r["value"] == 1
               for r in snap[obs.names.SLO_ALERTS]["series"])


def test_slo_multirate_rule_needs_both_windows():
    """Old badness only in the slow window must NOT alert: the fast
    window's recovery is exactly what the multi-rate rule listens to."""
    slo = obs.SLOTracker(_latency_spec(window_s=2.0, fast_window_frac=0.25))
    for _ in range(10):
        slo.record_request(0.5, ok=True)
    time.sleep(0.6)  # bad burst ages past the 0.5s fast window
    for _ in range(10):
        slo.record_request(0.01, ok=True)
    st = slo.evaluate()
    assert st["latency"]["burn_slow"] > 2.0  # slow window still burning
    assert not st["latency"]["alerting"]  # but the fast window recovered
    assert slo.alert_counts() == {}


def test_slo_availability_and_recall_objectives():
    spec = obs.SLOSpec(latency_p99_s=None, availability=0.9,
                       recall_floor=0.8, recall_budget=0.1,
                       window_s=5.0, min_samples=2)
    assert set(spec.budgets()) == {"availability", "recall"}
    slo = obs.SLOTracker(spec)
    for _ in range(8):
        slo.record_request(0.01, ok=False)  # every request fails
        slo.record_recall(0.5)  # every shadow sample under the floor
    slo.evaluate()
    counts = slo.alert_counts()
    assert counts.get("availability") == 1 and counts.get("recall") == 1


# --------------------------- report CLI + dashboard --------------------------


def _dump_registry(tmp_path):
    reg = MetricsRegistry(strict=False)
    reg.counter("router_req_total").inc(100)
    reg.histogram("router_lat_seconds").observe(0.05)
    path = tmp_path / "metrics.json"
    obs.MetricsDumper(reg, str(path), period_s=0).dump()
    return path


def test_report_cli_renders_text_and_html(tmp_path, capsys):
    path = _dump_registry(tmp_path)
    assert report_lib.main(["--metrics", str(path)]) == 0
    out = capsys.readouterr().out
    assert "observability report" in out and "router_req_total" in out
    html = tmp_path / "report.html"
    assert report_lib.main(["--metrics", str(path),
                            "--out", str(html)]) == 0
    text = html.read_text()
    assert text.startswith("<!doctype html>") and "router_req_total" in text


def test_report_cli_fails_on_missing_empty_or_malformed(tmp_path):
    assert report_lib.main(["--metrics", str(tmp_path / "nope.json")]) == 2
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert report_lib.main(["--metrics", str(empty)]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text('{"router_x_total": {"oops": 1}}')
    assert report_lib.main(["--metrics", str(bad)]) == 2
    noise = tmp_path / "noise.json"
    noise.write_text("not json at all")
    assert report_lib.main(["--metrics", str(noise)]) == 2
    with pytest.raises(report_lib.ReportError):
        report_lib.validate_snapshot([1, 2, 3])


def test_report_includes_trace_dump(tmp_path, capsys):
    path = _dump_registry(tmp_path)
    buf = obs.TraceBuffer(maxlen=4)
    sampler = obs.TraceSampler(1, buffer=buf)
    for seq in range(3):
        t = sampler.sample("request", seq)
        t.root.child("attempt").end()
        t.finish(outcome="ok")
    tpath = tmp_path / "traces.json"
    tpath.write_text(buf.to_json())
    assert report_lib.main(["--metrics", str(path),
                            "--trace", str(tpath)]) == 0
    out = capsys.readouterr().out
    assert "retained=3" in out and "attempt" in out


def test_dashboard_frame_renders_live_state(tmp_path):
    import io

    reg = MetricsRegistry(strict=False)
    reg.counter("router_requests_total").inc(42)
    slo = obs.SLOTracker(_latency_spec())
    slo.record_request(0.01, ok=True)
    slo.evaluate()
    stream = io.StringIO()
    dash = report_lib.Dashboard(reg, period_s=30.0, slo=slo,
                                stream=stream, clear=False)
    try:
        first = dash.frame()
        assert "served=42" in first and "slo[latency]" in first
        reg.counter("router_requests_total").inc(8)
        time.sleep(0.01)
        second = dash.frame()
        assert "served=50" in second and "qps=" in second
    finally:
        dash.close()
    assert "served=50" in stream.getvalue()  # close() emits a final frame
