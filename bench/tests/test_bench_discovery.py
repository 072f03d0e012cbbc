"""Every name in BENCHMARK.json finds its file, and a new file is found by
name alone."""

import json

import _paths
import pytest

from annbench import spec

BENCHMARK = spec.benchmark(_paths.ROOT)
FIXTURE = spec.load_json(_paths.FIXTURE)


@pytest.mark.parametrize("cell", [w["name"] for w in FIXTURE["workloads"]])
def test_every_cell_resolves(cell):
    bench = FIXTURE
    c = spec.cell(cell, bench)
    assert c["config"]["name"] == next(
        w for w in bench["workloads"] if w["name"] == cell)["config"]
    assert c["traffic"]["execution"] in ("beam", "two_stage")
    assert {"unanswered", "malformed"} <= set(c["traffic"]["limits"])
    assert "recall" in c["traffic"]["floors"]
    spec.recipe(c["config"]["recipe"])
    spec.distance(c["config"]["distance"])
    names = [m["name"] for m in c["per_layer"]]
    assert "build_s" in names
    for m in c["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
    assert "setup_s" in [m["name"] for m in c["end_to_end"]]


@pytest.mark.parametrize("bench", [BENCHMARK, FIXTURE],
                         ids=["committed", "with_pending_cells"])
def test_metric_workloads_name_real_cells(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


@pytest.mark.parametrize("bench", [BENCHMARK, FIXTURE],
                         ids=["committed", "with_pending_cells"])
def test_config_files_are_their_entries(bench):
    for c in bench["configs"]:
        cfg = json.loads((_paths.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("kernel", ["rank_pallas", "scan_pallas"])
def test_kernel_files_resolve(kernel):
    km = spec.kernel(kernel)
    assert km.TRACE_NAME and callable(km.calls) and callable(km.cost)


def test_new_metric_file_is_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new.layer_ms.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    monkeypatch.setattr(spec, "BENCH", tmp_path)
    assert spec.metric_reader("new.layer_ms").read({"x": 21}) == 42


@pytest.mark.parametrize("bad", ["../configs/x", "a b", "", "x/y"])
def test_names_outside_the_grammar_are_refused(bad):
    with pytest.raises(ValueError):
        spec.config(bad)
