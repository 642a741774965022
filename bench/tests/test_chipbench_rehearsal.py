"""A rehearsal of ``bench/run.py`` on the CPU: every cell refuses to
measure without a TPU, and prints no result."""
import json
import os
import subprocess
import sys

import pytest

from bench.lib import spec

CELLS = [w["name"] for w in json.load(
    open(os.path.join(spec.ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_no_tpu_means_no_result(cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         cell, "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "not a TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line
    assert "metrics" not in p.stdout


def test_without_the_program_a_run_fails(tmp_path):
    """A checkout that holds only the benchmark cannot run a cell."""
    import shutil
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[-1], "--seed",
         "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
