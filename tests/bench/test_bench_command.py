"""The command refuses, printing no result, where it cannot measure."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench import cells


def _env():
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    for key in ("HOME", "TMPDIR"):
        if key in os.environ:
            env[key] = os.environ[key]
    return env


def _run(root, *args):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sim-fig14",
         "--seed", "2147483659", "--seconds", "1", *args],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=120)


def test_no_result_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    for p in cells.load_benchmark()["paths"]:
        shutil.copytree(cells.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    got = _run(tmp_path)
    assert got.returncode != 0 and got.stdout == ""
    assert "program is not beside" in got.stderr


def test_no_result_without_a_tpu():
    got = _run(cells.ROOT)
    assert got.returncode != 0 and got.stdout == ""
    assert "no TPU" in got.stderr
