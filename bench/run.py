"""Run one cell of the benchmark once, on the chip, and print its result line.

    python bench/run.py --workload glove100-beam --seed 7 --seconds 20 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/workloads``). The run makes
its data on the device from ``--seed``, builds the index, warms the one
compiled batch shape, drives an open loop of requests through the serving
engine for ``--seconds``, checks every answer against a plain exact
reference, and prints one JSON line last on standard output: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics (from a
profiler trace of the window) with ``--trace 1``.

Without a TPU, or with fewer chips than the cell asks for, it exits with a
non-zero code and prints no result. JAX's compilation cache is kept in
``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT / "bench"))
    sys.path.insert(0, str(ROOT / "src"))
    from annbench import jaxenv, spec

    cell = spec.cell(args.workload, spec.benchmark(ROOT))
    if not (ROOT / "src" / "repro").is_dir():
        print("[bench] the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2

    jax = jaxenv.configure(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[bench] no TPU: JAX sees {devices[0].platform} devices; the "
              f"benchmark measures the chip and has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"[bench] {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 1

    from annbench import cell as cell_lib

    result = cell_lib.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
