"""Open-loop load: a fixed schedule of requests, timed from when each was due.

The schedule holds ``round(rate * seconds)`` requests whose due times are
the order statistics of uniform draws over the window (a Poisson process
conditioned on its count), each asking for a pool row drawn uniformly with
replacement. Every seed offers the same number of requests; only their
times and rows change.

One thread submits each request when it falls due and never waits for an
answer. A request's latency runs from its due time to its completion, so a
stall delays every request due during it, and how late the submitting
thread ran is reported beside it.

Answers are copied into arrays made before the window, and the objects
that set-up left behind are frozen out of the garbage collector's scans
(``quiesce``), so that the benchmark's own bookkeeping does not make the
collector stop the serving process for a full pass in the window.
"""

from __future__ import annotations

import dataclasses
import gc
import threading
import time
from typing import Callable

import jax
import numpy as np


def schedule(seed: int, *, rate: float, seconds: float, pool: int):
    """(due offsets [N] seconds ascending, pool rows [N])."""
    rng = np.random.default_rng(seed)
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    rows = rng.integers(0, pool, n)
    return due, rows


@dataclasses.dataclass
class Outcome:
    start: float  # perf_counter at the window's start (due offset 0)
    seconds: float
    due: np.ndarray  # [N] offsets
    done: np.ndarray  # [N] perf_counter at completion; NaN = none
    ok: np.ndarray  # [N] bool: answered without error
    lag: np.ndarray  # [N] seconds the submit ran after its due time

    def latencies(self, give_up: float) -> np.ndarray:
        """Seconds from due to completion; requests that failed or never
        completed read ``give_up`` seconds past the window's close."""
        lat = self.done - (self.start + self.due)
        horizon = self.start + self.seconds + give_up - (self.start + self.due)
        return np.where(self.ok & np.isfinite(lat), lat, horizon)

    def completed_in_window(self) -> int:
        return int((self.ok & (self.done <= self.start + self.seconds)).sum())


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (a value that was observed)."""
    s = np.sort(values)
    return float(s[max(int(np.ceil(q / 100.0 * len(s))) - 1, 0)])


def quiesce() -> None:
    """Collect, then freeze every object alive now out of later scans."""
    gc.collect()
    gc.freeze()


class GcPauses:
    """Count and time the collector's passes while it is open."""

    def __init__(self):
        self.count, self.total, self.longest = 0, 0.0, 0.0
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.count += 1
            self.total += d
            self.longest = max(self.longest, d)

    def close(self) -> str:
        gc.callbacks.remove(self._cb)
        return (f"{self.count} collector passes, {1e3 * self.total:.1f} ms, "
                f"longest {1e3 * self.longest:.1f} ms")


def run(submit: Callable, payloads: np.ndarray, due: np.ndarray, *,
        seconds: float, on_result: Callable, give_up: float = 60.0,
        on_start: Callable = None) -> Outcome:
    """Submit ``payloads[i]`` at ``due[i]`` through ``submit(payload,
    on_done=...)``, hand each answer to ``on_result(i, result)``, and wait
    for every answer up to ``give_up`` seconds past the window's close.
    ``on_start(start)`` learns the window's start (perf_counter)."""
    n = len(due)
    done = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    lag = np.zeros(n)
    left = [n]
    lock = threading.Lock()
    all_done = threading.Event()
    if n == 0:
        all_done.set()

    def finished(i, req):
        done[i] = time.perf_counter()
        if req.error is None:
            on_result(i, req.result)
            ok[i] = True
        with lock:
            left[0] -= 1
            if left[0] == 0:
                all_done.set()

    start = time.perf_counter() + 0.01
    if on_start is not None:
        on_start(start)
    for i in range(n):
        target = start + due[i]
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
            now = time.perf_counter()
        lag[i] = now - target
        with jax.profiler.TraceAnnotation("loadgen.submit"):
            submit(payloads[i], on_done=lambda r, i=i: finished(i, r))
    all_done.wait(max(start + seconds + give_up - time.perf_counter(), 0.0))
    return Outcome(start=start, seconds=seconds, due=due, done=done.copy(),
                   ok=ok.copy(), lag=lag)
