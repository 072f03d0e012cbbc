"""Readings of the control (the reference in the program's place, one
precision step down) and of faults planted under the timed path.

    python bench/control.py --workload glove100-beam --seeds 3,4,5 --seconds 20
    python bench/control.py --workload glove100-beam --seeds 3,4,5 \
        --seconds 10 --faults descent_path,descent_path_half

For each seed, the run's own data and request schedule are made as
``run.py`` makes them; then the plain reference answers every request due
in the window twice, by its Gram pass at ``highest`` (the reference
itself, read as if it were the program) and at ``bf16_3x`` (float32
products in three bf16 passes, as ``Precision.HIGH`` computes them on a
TPU: the control). Both answers go through the same comparison that
decides ``correct``, and one JSON line per seed prints the numbers beside
the cell's limits. The control has to come out not correct. Needs no
index and no window, so it costs a run's data and reference only.

With ``--faults``, each seed instead makes one whole run of the cell per
fault (``annbench/faults.py``) at the cell's own rate and size, and prints
its checks: each fault has to come out not correct.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def readings(cell: dict, seed: int, seconds: float) -> dict:
    """{precision: compared numbers} for one seed."""
    from annbench import cell as cell_lib, check, loadgen, reference, spec

    cfg, traffic = cell["config"], cell["traffic"]
    train, pool = cell_lib.make_data(cfg, seed)
    due, rows = loadgen.schedule(seed, rate=traffic["rate_qps"],
                                 seconds=seconds, pool=len(pool))
    db = reference.Database(train, spec.distance(cfg["distance"]))
    out = {}
    for precision in reference.PRECISIONS:
        d, i = db.topk(pool, cfg["k"], precision=precision)
        numbers = check.compare(db, pool, rows, np.ones(len(rows), bool),
                                i[rows], d[rows], cfg["k"])
        correct, checks = check.verdict(numbers, traffic["limits"],
                                        traffic.get("floors"))
        out[precision] = dict(correct=correct, **numbers)
    return out


def fault_readings(cell: dict, fault: str, seed: int, seconds: float
                   ) -> dict:
    """One whole run of ``cell`` with ``fault`` planted: its checks."""
    from annbench import cell as cell_lib, faults

    res = cell_lib.run(cell, seed=seed, seconds=seconds, trace=False,
                       t_start=time.perf_counter(),
                       fault=faults.make(fault, cell["config"]))
    return dict(correct=res["correct"], checks=res["checks"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--faults", default="")
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT / "bench"))
    sys.path.insert(0, str(ROOT / "src"))
    from annbench import jaxenv

    jax = jaxenv.configure(ROOT)
    from annbench import spec

    if jax.devices()[0].platform != "tpu":
        print("[control] no TPU", file=sys.stderr)
        return 1
    cell = spec.cell(args.workload, spec.benchmark(ROOT))
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.faults:
            for name in args.faults.split(","):
                print(json.dumps(dict(workload=args.workload, seed=seed,
                                      fault=name, **fault_readings(
                                          cell, name, seed, args.seconds))),
                      flush=True)
        else:
            print(json.dumps(dict(workload=args.workload, seed=seed,
                                  **readings(cell, seed, args.seconds))),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
