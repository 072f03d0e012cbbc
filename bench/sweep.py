"""Find a cell's knee: the highest offered rate its engine keeps up with.

    python bench/sweep.py --workload glove100-beam --seed 11 \
        --rates 800,1200,1600,2000,2400 --step-seconds 8

Builds the cell's index once, then offers each rate in turn for one step
of the same open loop the benchmark drives, and prints one JSON line per
step. A step keeps up when the requests due in its last quarter wait no
longer than those due in its first (``growth`` <= 1.5), all but 3% of them
finished inside the step (those due in its last latency do not), and its
p99 latency stays within twice the first step's (so the first rate is a
light load). The knee is the highest such rate. Steps as long as the
benchmark's window see what a window sees: a stall of the host that
leaves a backlog the engine does not work off in time fails the step. The
cell's fixed rate is set to 0.8 x knee by hand, in its traffic file. Runs
on the chip only, like ``run.py``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
GROWTH_MAX = 1.5
FINISHED_MIN = 0.97
P99_MAX = 2.0  # x the first (light-load) step's p99


def main(argv=None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--step-seconds", type=float, default=8.0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT / "bench"))
    sys.path.insert(0, str(ROOT / "src"))
    from annbench import jaxenv

    jax = jaxenv.configure(ROOT)
    from annbench import cell as cell_lib, loadgen, spec

    if jax.devices()[0].platform != "tpu":
        print("[sweep] no TPU", file=sys.stderr)
        return 1
    cell = spec.cell(args.workload, spec.benchmark(ROOT))
    cfg, traffic = cell["config"], cell["traffic"]
    train, pool = cell_lib.make_data(cfg, args.seed)
    idx, build_s = cell_lib.build(cfg, traffic, train)
    engine = cell_lib.engine_for(idx, cfg, traffic)
    cell_lib.warm(engine, pool, traffic["batch"])
    cell_lib.log(f"build_s {build_s:.2f}, set-up "
                 f"{time.perf_counter() - t_start:.1f}s")
    knee, misses, base_p99 = None, 0, None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        due, rows = loadgen.schedule(args.seed + i, rate=rate,
                                     seconds=args.step_seconds,
                                     pool=len(pool))
        before = cell_lib._engine_hists()
        loadgen.quiesce()
        out = loadgen.run(lambda q, on_done: engine.submit(q, on_done=on_done),
                          pool[rows], due, seconds=args.step_seconds,
                          on_result=lambda i, r: None, give_up=30.0)
        gc.unfreeze()
        after = cell_lib._engine_hists()
        lat = out.latencies(30.0)
        quarter = max(len(lat) // 4, 1)
        growth = float(np.mean(lat[-quarter:]) / np.mean(lat[:quarter]))
        finished = out.completed_in_window() / max(len(due), 1)
        occ = ((after["occupancy"][0] - before["occupancy"][0])
               / max(after["occupancy"][1] - before["occupancy"][1], 1))
        p99 = loadgen.percentile(lat, 99)
        base_p99 = p99 if base_p99 is None else base_p99
        keeps_up = (growth <= GROWTH_MAX and finished >= FINISHED_MIN
                    and p99 <= P99_MAX * base_p99)
        if keeps_up:
            knee, misses = rate, 0
        else:
            misses += 1
        print(json.dumps(dict(
            rate_qps=rate, requests=len(due),
            completed_qps=out.completed_in_window() / args.step_seconds,
            p50_ms=1e3 * loadgen.percentile(lat, 50),
            p99_ms=1e3 * p99,
            growth=growth, finished=finished, occupancy=occ,
            lag_p99_ms=1e3 * loadgen.percentile(out.lag, 99),
            lag_max_ms=1e3 * float(out.lag.max()),
            lag_max_at_s=float(out.due[int(out.lag.argmax())]),
            keeps_up=keeps_up)), flush=True)
        if misses == 2:
            break
    engine.close()
    print(json.dumps(dict(workload=args.workload, knee_qps=knee,
                          rate_qps=None if knee is None else 0.8 * knee)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
