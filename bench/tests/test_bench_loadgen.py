"""Open-loop timing: latency runs from the due time, so a stall shows in
every request due during it, and the schedule offers the same count on
every seed."""

import threading
import time

import _paths  # noqa: F401
import numpy as np

from annbench import loadgen


class _FakeEngine:
    """One worker answering requests in arrival order; the request due at
    ``stall_at`` holds the worker for ``stall`` seconds."""

    def __init__(self, stall_at: int, stall: float):
        self.q = []
        self.cv = threading.Condition()
        self.stall_at, self.stall = stall_at, stall
        self.t = threading.Thread(target=self._work, daemon=True)
        self.t.start()

    def submit(self, payload, on_done):
        with self.cv:
            self.q.append((payload, on_done))
            self.cv.notify()

    def _work(self):
        while True:
            with self.cv:
                while not self.q:
                    self.cv.wait()
                payload, on_done = self.q.pop(0)
            if payload == self.stall_at:
                time.sleep(self.stall)

            class R:
                error = None
                result = payload

            on_done(R)
            if payload == -1:
                return


def test_schedule_is_fixed_in_count_and_seeded():
    a = loadgen.schedule(2 ** 40 + 3, rate=500, seconds=2.0, pool=64)
    b = loadgen.schedule(2 ** 40 + 3, rate=500, seconds=2.0, pool=64)
    c = loadgen.schedule(7, rate=500, seconds=2.0, pool=64)
    assert len(a[0]) == len(c[0]) == 1000
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert (np.diff(a[0]) >= 0).all() and a[0].max() < 2.0
    assert a[1].min() >= 0 and a[1].max() < 64


def test_stall_delays_the_requests_due_during_it():
    n, seconds, stall = 100, 1.0, 0.3
    due = np.linspace(0.0, seconds, n, endpoint=False)
    eng = _FakeEngine(stall_at=20, stall=stall)
    payloads = np.arange(n)
    payloads[-1] = -1
    got = np.full(n, -2)

    def on_result(i, r):
        got[i] = r

    out = loadgen.run(eng.submit, payloads, due, seconds=seconds,
                      on_result=on_result, give_up=5.0)
    eng.t.join(timeout=5.0)
    assert not eng.t.is_alive()
    assert out.ok.all()
    assert np.array_equal(got, payloads)
    lat = out.latencies(5.0)
    # the stalled request and the ones due during the stall wait; the
    # wait shrinks as their due times approach the stall's end
    assert lat[20] >= stall * 0.9
    assert lat[21] >= stall * 0.8 and lat[35] >= stall * 0.4
    assert lat[21] > lat[30] > lat[40]
    # well after the stall the worker has caught up
    assert np.median(lat[60:]) < 0.05
    assert loadgen.percentile(lat, 99) >= 0.2
    assert out.completed_in_window() == n


def test_unanswered_requests_count_past_the_close():
    due = np.array([0.0, 0.01])

    def submit(payload, on_done):
        if payload == 0:
            class R:
                error = None
                result = 0
            on_done(R)

    out = loadgen.run(submit, np.arange(2), due, seconds=0.05,
                      on_result=lambda i, r: None, give_up=0.05)
    assert out.ok.tolist() == [True, False]
    lat = out.latencies(0.05)
    assert lat[1] >= 0.05 + 0.05 - 0.01 - 1e-6
    assert out.completed_in_window() == 1


def test_gc_pauses_are_counted():
    pauses = loadgen.GcPauses()
    loadgen.quiesce()
    note = pauses.close()
    assert pauses.count >= 1 and "collector passes" in note
    assert loadgen.GcPauses._cb not in [getattr(c, "__func__", None)
                                         for c in __import__("gc").callbacks]


def test_percentile_is_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert loadgen.percentile(v, 99) == 99.0
    assert loadgen.percentile(v, 50) == 50.0
    assert loadgen.percentile(np.array([3.0]), 99) == 3.0
