"""A run with its timed path broken underneath comes out not correct, and
the control (the reference one precision step down) does too.

These drive the harness on the CPU at a small size: the look for a chip is
in ``run.py``, and everything after it (data, build, engine, open loop,
check) is ``cell.run``. Faults a serving cell can have
(``annbench/faults.py``): an answer altered where it is produced, half of
a batch left out (its rows answered with another row's answer), and a
descent down another query's path. One chip exchanges nothing, and a
server keeps no training state.
"""

import importlib.util
import time

import _paths
import pytest

from annbench import cell as cell_lib, faults, spec

SMALL = dict(n=4096, pool=128, gl=64)
FIXTURE = spec.load_json(_paths.FIXTURE)


def _cell(workload):
    c = spec.cell(workload, FIXTURE)
    c["config"].update(SMALL)
    c["traffic"]["rate_qps"] = 100
    return c


def _run(workload, fault, seed=2 ** 35 + 9):
    c = _cell(workload)
    return cell_lib.run(c, seed=seed, seconds=1.0, trace=False,
                        t_start=time.perf_counter(),
                        fault=fault and faults.make(fault, c["config"]),
                        give_up=10.0)


def test_sound_run_is_correct():
    res = _run("glove100-beam", None)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 100 and res["failed"] == 0
    assert list(res["checks"]) == ["unanswered", "malformed",
                                   "dist_gap_max", "dist_gap_mean", "recall"]
    assert set(res["metrics"]) == {"qps", "p95_ms", "recall_at_10",
                                   "hbm_bytes_per_vector", "setup_s"}


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("workload", ["glove100-beam", "glove100-two_stage",
                                      "nytimes256-beam"])
def test_broken_timed_path_is_not_correct(workload, fault):
    res = _run(workload, fault)
    assert not res["correct"]
    c = res["checks"]
    assert c["dist_gap_max"]["value"] > c["dist_gap_max"]["max"]


@pytest.mark.parametrize("workload", ["glove100-beam", "nytimes256-beam"])
def test_descent_down_another_path_is_not_correct(workload):
    """Well-formed ids with true distances: only the recall floor sees it."""
    res = _run(workload, "descent_path")
    assert not res["correct"]
    c = res["checks"]
    assert c["malformed"]["value"] == 0
    assert c["dist_gap_max"]["value"] <= c["dist_gap_max"]["max"]
    assert c["recall"]["value"] < c["recall"]["min"]


def _control():
    path = _paths.BENCH / "control.py"
    s = importlib.util.spec_from_file_location("_bench_control", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("workload", ["glove100-beam", "nytimes256-beam"])
def test_control_is_not_correct(workload):
    c = spec.cell(workload, FIXTURE)
    c["config"].update(n=32768, pool=256)
    r = _control().readings(c, 2 ** 34 + 17, 2.0)
    assert r["highest"]["correct"], r["highest"]
    assert not r["bf16_3x"]["correct"], r["bf16_3x"]
    assert r["bf16_3x"]["dist_gap_mean"] > \
        c["traffic"]["limits"]["dist_gap_mean"]


def test_traced_run_reports_per_layer_metrics():
    c = _cell("glove100-two_stage")
    res = cell_lib.run(c, seed=2 ** 36 + 3, seconds=1.0, trace=True,
                       t_start=time.perf_counter(), give_up=10.0)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert {"build_s", "engine.queue_wait_ms", "engine.batch_occupancy",
            "pipeline.handler_ms", "two_stage.rerank_ms"} <= set(m)
    assert "qps" not in m
    assert 0 < m["engine.batch_occupancy"]["value"] <= 1
    assert m["two_stage.rerank_ms"]["value"] > 0
    dev = res["device"]
    assert dev["window_s"] == pytest.approx(1.0, abs=0.2)
    assert len(res["breakdown"]["idle_gaps"]) <= 10
    assert list(res)[-1] == "checks"
