"""The command refuses to measure where it cannot: no chip, no program."""

import json
import os
import shutil
import subprocess
import sys

from bench import run as bench_run

ROOT = bench_run.ROOT


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "sift1m-flat.single", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except json.JSONDecodeError:
        return True
    return False


def test_no_chip_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    proc = _run(tmp_path, "--rehearse-n", "2000")
    assert proc.returncode != 0
    assert _no_result(proc)
