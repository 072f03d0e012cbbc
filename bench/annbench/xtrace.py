"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
event lists; everything else works on those lists, so the reduction is
checked on a small recorded trace (``bench/tests/data``) without a chip.

    device ops: [(name, start_ns, duration_ns)] from the first TPU plane's
                "XLA Ops" line (every operation the device ran), named by
                its HLO instruction (``rank_pallas.5``, ``fusion.50``)
    host:       [(line, name, start_ns, duration_ns)] from the host plane
                (the benchmark's TraceAnnotations and the runtime's own)
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HANDLER_SPAN = "engine.handler"
WINDOW_SPAN = "bench.window"


def op_name(text: str) -> str:
    """The HLO instruction's name from an op event's text, which is the
    whole instruction (``%rank_pallas.5 = (f32[...]) custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str) -> dict:
    """Event lists of the one trace under ``log_dir``."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device, host = [], []
    planes = sorted(
        (p for p in data.planes if DEVICE_PLANE.match(p.name)),
        key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    for plane in planes[:1]:
        for line in plane.lines:
            if line.name == OPS_LINE:
                device.extend((op_name(e.name), float(e.start_ns),
                               float(e.duration_ns)) for e in line.events)
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                host.extend((i, e.name, float(e.start_ns),
                             float(e.duration_ns)) for e in line.events)
    return dict(device=device, host=host,
                device_planes=[p.name for p in planes])


def window(trace: dict) -> tuple[float, float]:
    """(start_ns, end_ns) of the benchmark's window span."""
    spans = [(s, s + d) for _, name, s, d in trace["host"]
             if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the "
                           f"trace, found {len(spans)}")
    return spans[0]


def clip(device, window):
    """The device ops that overlap ``window``, cut to it."""
    lo, hi = window
    return [(name, max(s, lo), min(s + d, hi) - max(s, lo))
            for name, s, d in device if s + d > lo and s < hi]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(device, window):
    """Union of device-op intervals clipped to ``window`` (ns pair)."""
    lo, hi = window
    clipped = ((max(s, lo), min(s + d, hi)) for _, s, d in device)
    return _merge(iv for iv in clipped if iv[1] > iv[0])


def busy_seconds(device, window) -> float:
    return sum(e - s for s, e in busy_intervals(device, window)) / 1e9


def idle_gaps(device, window):
    """[(start_ns, end_ns)] of the window in which no device op ran."""
    lo, hi = window
    gaps, cur = [], lo
    for s, e in busy_intervals(device, window):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def op_seconds(device, pattern=None) -> dict:
    """Device seconds per op name (names matching ``pattern`` only)."""
    rx = re.compile(pattern) if pattern else None
    out: dict = collections.defaultdict(float)
    for name, _, d in device:
        if rx is None or rx.search(name):
            out[name] += d / 1e9
    return dict(out)


def count_ops(device, pattern) -> tuple[int, float]:
    """(events, device seconds) of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [d for name, _, d in device if rx.search(name)]
    return len(hits), sum(hits) / 1e9


class HostIndex:
    """What the serving engine's worker thread was doing at a time: the
    innermost host event on its line (the line that holds the benchmark's
    ``engine.handler`` spans), prefixed by whether it lay inside a handler
    call (``handler/``) or outside one (``worker/``: batch assembly, the
    wait for results, handing them out, or waiting for requests)."""

    LOOKBACK = 256  # events scanned back for the innermost cover

    def __init__(self, host):
        lines = collections.Counter(ln for ln, name, _, _ in host
                                    if name == HANDLER_SPAN)
        self.line = lines.most_common(1)[0][0] if lines else None
        evs = sorted((s, s + d, name) for ln, name, s, d in host
                     if ln == self.line)
        self.evs = evs
        self.starts = [s for s, _, _ in evs]

    def label(self, t: float) -> str:
        if self.line is None:
            return "no engine span"
        j = bisect.bisect_right(self.starts, t) - 1
        inner, in_handler = None, False
        for s, e, name in self.evs[max(j - self.LOOKBACK, 0):j + 1]:
            if s <= t < e:
                if name == HANDLER_SPAN:
                    in_handler = True
                elif inner is None or e - s < inner[0]:
                    inner = (e - s, name)
        where = "handler" if in_handler else "worker"
        return f"{where}/{inner[1][:80]}" if inner else f"{where}/no event"


def breakdown(trace: dict, window, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time,
    and the idle gaps summed by what the host was doing in them."""
    ops = sorted(op_seconds(trace["device"]).items(),
                 key=lambda kv: -kv[1])[:top]
    index = HostIndex(trace["host"])
    by_label: dict = collections.defaultdict(float)
    for s, e in idle_gaps(trace["device"], window):
        by_label[index.label((s + e) / 2)] += (e - s) / 1e9
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[n, v] for n, v in ops],
                idle_gaps=[[n, v] for n, v in gaps])
