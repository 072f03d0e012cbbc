"""The serving engine's span readers on 20 ms of a recorded v5e trace
(glove100-beam, with the program's engine spans in the host plane)."""

import json
import statistics

import _paths
import numpy as np
import pytest

from annbench import spans, spec, xtrace

TRACE = json.loads((_paths.BENCH / "tests" / "data"
                    / "trace_v5e_glove100_beam_spans.json").read_text())
WINDOW = xtrace.window(TRACE)
DEVICE = xtrace.clip(TRACE["device"], WINDOW)
WINDOW_S = (WINDOW[1] - WINDOW[0]) / 1e9


def _ctx(host=None):
    return dict(trace=dict(device=DEVICE, host=host or TRACE["host"]),
                window_s=WINDOW_S,
                busy_s=xtrace.busy_seconds(DEVICE, WINDOW))


def _grid(intervals, step=100.0):
    """A 100 ns grid of the window marking ``intervals``: an independent
    reading of the sweep in ``spans``."""
    lo, hi = WINDOW
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for s, e in intervals:
        a = int(np.floor((max(s, lo) - lo) / step))
        b = int(np.ceil((min(e, hi) - lo) / step))
        grid[max(a, 0):max(b, 0)] = True
    return grid


def _engine(names):
    return [(s, s + d) for _, n, s, d in TRACE["host"] if n in names]


def test_stage_spans_follow_each_other_on_one_line():
    evs = sorted((s, n, ln) for ln, n, s, _ in TRACE["host"]
                 if n in spans.STAGES)
    assert len({ln for _, _, ln in evs}) == 1
    names = [n for _, n, _ in evs]
    first = names.index(spans.TAKE)
    cycle = list(spans.STAGES)
    for i, n in enumerate(names[first:]):
        assert n == cycle[i % len(cycle)]
    assert names[first:first + len(cycle)] == cycle  # one whole batch


@pytest.mark.parametrize("metric,stage", [
    ("engine.dispatch_ms", "engine.dispatch"),
    ("engine.fetch_ms", "engine.fetch"),
    ("engine.deliver_ms", "engine.deliver"),
])
def test_stage_mean_readers(metric, stage):
    lo, hi = WINDOW
    want = [d for _, n, s, d in TRACE["host"] if n == stage and lo <= s < hi]
    assert want
    got = spec.metric_reader(metric).read(_ctx())
    assert got == pytest.approx(statistics.mean(want) / 1e6)


def test_idle_splits_into_traffic_host_and_no_span():
    split = spans.idle_split(_ctx()["trace"])
    idle = sum(e - s for s, e in xtrace.idle_gaps(DEVICE, WINDOW)) / 1e9
    assert sum(split.values()) == pytest.approx(idle)
    assert min(split.values()) >= 0.0 and split["host"] > 0.0
    idle_grid = ~_grid((s, s + d) for _, s, d in DEVICE)
    for key, names in (("take_batch", {spans.TAKE}),
                       ("host", set(spans.STAGES[1:]))):
        want = (idle_grid & _grid(_engine(names))).sum() * 100e-9
        assert split[key] == pytest.approx(want, abs=2e-6)


def test_idle_split_on_a_drawn_trace():
    # device busy [10, 40) and [70, 90) of a 100-ns window; the worker
    # waits for traffic, works a batch, leaves a 3-ns hole, waits again
    host = [(7, "bench.window", 0.0, 100.0), (3, "other", 0.0, 100.0)] + [
        (4, name, float(s), float(e - s)) for name, s, e in (
            ("engine.take_batch", 0, 20), ("engine.stack", 20, 25),
            ("engine.dispatch", 25, 30), ("engine.device_wait", 30, 50),
            ("engine.fetch", 50, 60), ("engine.deliver", 60, 62),
            ("engine.take_batch", 65, 100))]
    trace = dict(device=[("a", 10.0, 30.0), ("b", 70.0, 20.0)], host=host)
    split = spans.idle_split(trace)
    assert split == pytest.approx(dict(take_batch=25e-9, host=22e-9,
                                       none=3e-9))
    assert spans.mean_ms(trace, "engine.fetch") == pytest.approx(10e-6)


def test_host_idle_share_reader():
    share = spec.metric_reader("engine.host_idle_share").read(_ctx())
    split = spans.idle_split(_ctx()["trace"])
    assert share == pytest.approx(100.0 * split["host"] / WINDOW_S)
    device_idle = spec.metric_reader("device.idle_share").read(_ctx())
    assert 0.0 < share <= device_idle


@pytest.mark.parametrize("metric", ["engine.dispatch_ms", "engine.fetch_ms",
                                    "engine.deliver_ms",
                                    "engine.host_idle_share"])
def test_readers_are_silent_without_engine_spans(metric):
    host = [e for e in TRACE["host"] if e[1] not in spans.STAGES]
    reader = spec.metric_reader(metric)
    assert reader.read(_ctx(host)) is None
    assert reader.read(dict(_ctx(), trace=None)) is None


def test_engine_metrics_are_reported_in_the_traced_cell():
    bench = spec.benchmark(_paths.ROOT)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in ("engine.dispatch_ms", "engine.fetch_ms",
                            "engine.deliver_ms", "engine.host_idle_share")}
    assert len(new) == 4
    for m in new.values():
        assert m["workloads"] == ["glove100-beam"]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "serving engine", "p95_ms")
    cell = spec.cell("glove100-beam", bench)
    assert set(new) <= {m["name"] for m in cell["per_layer"]}
