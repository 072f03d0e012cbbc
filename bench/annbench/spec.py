"""Find a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer
metric, kernel, data recipe or distance sits in a file of its own under
``bench/``; this module is the only place that maps a name to its file, so
a new cell, config or metric is a new file plus a ``BENCHMARK.json`` entry.

    bench/configs/<config>.json       sizes of a deployment
    bench/workloads/<traffic>.json    one traffic mix (rate, execution, limits)
    bench/recipes/<recipe>.py         device data generator named by a config
    bench/distances/<distance>.py     plain reference distance of a config
    bench/metrics/<metric>.py         reader of one per-layer metric
    bench/kernels/<kernel>.py         operations and bytes of one kernel
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _check_name(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{_check_name(name)}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "workloads" / f"{_check_name(name)}.json")


def _module(kind: str, name: str):
    path = BENCH / kind / f"{_check_name(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    mod_name = f"_bench_{kind}_" + re.sub(r"[^A-Za-z0-9_]", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def recipe(name: str):
    return _module("recipes", name)


def distance(name: str):
    return _module("distances", name)


def metric_reader(name: str):
    return _module("metrics", name)


def kernel(name: str):
    return _module("kernels", name)


def cell(workload: str, bench: dict | None = None) -> dict:
    """Everything one run of ``workload`` needs: the cell entry, its config
    and traffic files, and the metrics it reports (end-to-end for
    ``--trace 0``, per-layer for ``--trace 1``)."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return dict(
        name=workload,
        chips=int(w["chips"]),
        config=config(w["config"]),
        traffic=traffic(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )
