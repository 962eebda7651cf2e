"""``bench.run`` refuses to run without a TPU, and without the program."""

import json
import os
import shutil
import subprocess
import sys

from bench import run as bench_run

ROOT = bench_run.ROOT
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
ARGS = ["-m", "bench.run", "--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_backend():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_refuses_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
