"""The entry point prints no result and exits non-zero without a TPU, and
in a checkout that holds the benchmark alone."""

import os
import shutil
import subprocess
import sys

import _paths


def _run(root, workload="glove100-beam"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         workload, "--seed", str(2 ** 40 + 1), "--seconds", "1", "--trace",
         "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run(_paths.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_is_not_enough(tmp_path):
    shutil.copy(_paths.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert not (tmp_path / ".jax_cache").exists()
