"""The serving engine's stage spans in a profiler trace.

The program opens one span per stage of each batch on the engine's worker
thread, and each span is also a profiler annotation of the same name, so
they sit in ``trace["host"]`` beside the runtime's own events:

    engine.take_batch   blocked on the queue, then filling the batch
    engine.stack        padding and stacking the payloads
    engine.dispatch     the handler call; returns before the device is done
    engine.device_wait  waiting for the batch's results on the device
    engine.fetch        the device-to-host copy of the results
    engine.deliver      a row to each request, callbacks, batch metrics

A program without these spans leaves every reading here ``None``.
"""

from __future__ import annotations

from annbench import xtrace

STAGES = ("engine.take_batch", "engine.stack", "engine.dispatch",
          "engine.device_wait", "engine.fetch", "engine.deliver")
TAKE = STAGES[0]


def mean_ms(trace: dict, name: str):
    """Mean duration (ms) of the ``name`` spans that start in the window;
    ``None`` when none does."""
    lo, hi = xtrace.window(trace)
    ds = [d for _, n, s, d in trace["host"] if n == name and lo <= s < hi]
    return sum(ds) / len(ds) / 1e6 if ds else None


def _worker(trace: dict, names, window):
    """Intervals of the spans in ``names`` on the worker's line (the line
    that holds ``engine.dispatch``), cut to ``window``, merged."""
    lo, hi = window
    lines = {ln for ln, n, _, _ in trace["host"] if n == STAGES[2]}
    if len(lines) > 1:
        raise RuntimeError(f"engine spans on {len(lines)} lines; the "
                           f"reading assumes one engine worker")
    return xtrace._merge(
        (max(s, lo), min(s + d, hi)) for ln, n, s, d in trace["host"]
        if ln in lines and n in names and s + d > lo and s < hi)


def _overlap_ns(a, b) -> float:
    """Total length of the intersection of two sorted disjoint lists of
    (start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_split(trace: dict):
    """Seconds of the window in which the device is idle, split by where
    the engine's worker is: ``take_batch`` (waiting for traffic),
    ``host`` (inside any other engine span: the host's own work) and
    ``none`` (in no engine span). ``None`` without engine spans."""
    window = xtrace.window(trace)
    take = _worker(trace, {TAKE}, window)
    host = _worker(trace, set(STAGES[1:]), window)
    if not take and not host:
        return None
    outside = xtrace.idle_gaps([(None, s, e - s) for s, e in take + host],
                               window)
    gaps = xtrace.idle_gaps(trace["device"], window)
    return {key: _overlap_ns(gaps, iv) / 1e9 for key, iv in
            (("take_batch", take), ("host", host), ("none", outside))}
