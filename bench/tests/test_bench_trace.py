"""The trace reduction on 20 ms of a recorded v5e trace (glove100-beam)."""

import json

import _paths
import numpy as np
import pytest

from annbench import xtrace

TRACE = json.loads((_paths.BENCH / "tests" / "data"
                    / "trace_v5e_glove100_beam.json").read_text())
WINDOW = tuple(TRACE["window"])
DEVICE = xtrace.clip(TRACE["device"], WINDOW)


def _busy_by_grid(events, window, step=100.0):
    """Busy seconds counted on a 100 ns grid: an independent reading."""
    lo, hi = window
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for _, s, d in events:
        a = int(np.floor((s - lo) / step))
        b = int(np.ceil((s + d - lo) / step))
        grid[max(a, 0):max(b, 0)] = True
    return grid.sum() * step / 1e9


def test_busy_and_idle_share():
    busy = xtrace.busy_seconds(DEVICE, WINDOW)
    assert busy == pytest.approx(_busy_by_grid(DEVICE, WINDOW), rel=2e-2)
    gaps = xtrace.idle_gaps(DEVICE, WINDOW)
    idle = sum(e - s for s, e in gaps) / 1e9
    assert busy + idle == pytest.approx((WINDOW[1] - WINDOW[0]) / 1e9)
    assert 0.0 < idle / 0.02 < 1.0


def test_clip_keeps_ops_inside_the_window():
    lo, hi = WINDOW
    assert all(lo <= s and s + d <= hi + 1e-6 for _, s, d in DEVICE)
    half = (lo, lo + 10e6)
    assert xtrace.busy_seconds(xtrace.clip(DEVICE, half), half) <= \
        xtrace.busy_seconds(DEVICE, WINDOW)


def test_kernel_time_by_instruction_name():
    n, seconds = xtrace.count_ops(DEVICE, r"^rank_pallas(\.\d+)?$")
    # a served batch ranks 11 times: 10 descent levels and the leaf
    names = {name for name, _, _ in DEVICE if name.startswith("rank_pallas")}
    assert n > 0 and 0 < len(names) <= 11
    want = sum(d for name, _, d in DEVICE
               if name.startswith("rank_pallas")) / 1e9
    assert seconds == pytest.approx(want)
    per_name = xtrace.op_seconds(DEVICE, r"^rank_pallas")
    assert sum(per_name.values()) == pytest.approx(seconds)


def test_op_name_is_the_instruction():
    text = ("%rank_pallas.5 = (f32[32,32]{1,0}, s32[32,32]{1,0}) "
            "custom-call(f32[32,100]{1,0} %copy-done.47)")
    assert xtrace.op_name(text) == "rank_pallas.5"


def test_breakdown_lists_ops_and_labelled_gaps():
    b = xtrace.breakdown(dict(device=DEVICE, host=TRACE["host"]), WINDOW)
    ops = b["device_ops"]
    assert 0 < len(ops) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert ops[0][0].startswith("copy.")  # the per-batch relayout copies
    gaps = b["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(n.split("/")[0] in ("handler", "worker") for n, _ in gaps)
    total = sum(v for _, v in gaps)
    idle = sum(e - s for s, e in xtrace.idle_gaps(DEVICE, WINDOW)) / 1e9
    assert total == pytest.approx(idle)


def test_window_span_is_required():
    with pytest.raises(RuntimeError):
        xtrace.window(dict(host=[(0, "other", 0.0, 1.0)]))
    assert xtrace.window(dict(host=[(2, xtrace.WINDOW_SPAN, 5.0, 10.0)])) \
        == (5.0, 15.0)
