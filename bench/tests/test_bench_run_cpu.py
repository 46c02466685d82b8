"""bench/run.py on the CPU: it refuses to measure, and a tiny copy of each
cell runs end to end (without the look for a chip) and is correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import BENCH, run, run_tiny, tiny_cell

ROOT = os.path.dirname(BENCH)


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "series_burst",
         "--seed", "4000000000", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode == run.EXIT_NO_CHIP
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "series_drift",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(_cpu_env(), PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("name", ["series_drift", "series_burst"])
def test_tiny_cell_is_correct(name):
    out, lines = run_tiny(tiny_cell(name), seed=2**33 + 5)
    assert out["correct"], "\n".join(lines)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]
    json.dumps(out)
    assert any(line.startswith("check ") for line in lines[-3:])
    assert "compiles 0" in next(x for x in lines if "inside the window" in x)
