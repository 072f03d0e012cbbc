"""Put the benchmark's own package and the system under test on the path.

``FIXTURE`` is a benchmark file of the tests' own: the committed cell and
the cells whose files wait in ``bench/`` for a later entry in
``BENCHMARK.json`` (``glove100-two_stage``, ``nytimes256-beam``), so that
their configs, traffic files and metric readers stay exercised whatever
the committed file lists.
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURE = BENCH / "tests" / "data" / "benchmark.json"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
