"""CLI smoke coverage for the jax-heavy benchmark harnesses.

`benchmarks.hillclimb` and `benchmarks.roofline` were previously imported
by nothing in the suite, so suite-API refactors could break them invisibly.
Each runs ``--help`` in a subprocess (covering the full import chain —
jax, configs, sharding, train step) with `jax_subprocess_env`, which pins
``JAX_PLATFORMS`` so hosts with a TPU-less libtpu never hang probing for
accelerators.
"""
import pathlib
import subprocess
import sys

import pytest

from jax_subprocess import jax_subprocess_env

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("module", ["benchmarks.hillclimb",
                                    "benchmarks.roofline"])
def test_bench_cli_imports_and_help(module):
    r = subprocess.run(
        [sys.executable, "-m", module, "--help"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=jax_subprocess_env())
    assert r.returncode == 0, (module, r.stdout, r.stderr)
    assert "usage" in r.stdout.lower(), (module, r.stdout)


def test_bench_sim_help_lists_all_smoke_flags():
    """Every CI smoke entry point is wired into the bench_sim CLI (the full
    interval/bank sweeps run as their own CI steps, not in tier-1)."""
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sim", "--help"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=jax_subprocess_env())
    assert r.returncode == 0, (r.stdout, r.stderr)
    for flag in ("--smoke", "--gpu-smoke", "--bank-smoke",
                 "--interval-smoke", "--chaos-smoke", "--baseline",
                 "--suite"):
        assert flag in r.stdout, flag


def test_bench_sim_gpu_smoke_cli():
    """The CI GPU-scale smoke entry point stays runnable end to end."""
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sim", "--gpu-smoke"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=jax_subprocess_env())
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert '"gpu_sims"' in r.stdout and '"scheduler"' in r.stdout
