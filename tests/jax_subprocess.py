"""Environment for test subprocesses that import jax.

Only tests use this.  A test runs in a process that already holds its
backend, so a child it starts must never reach for the accelerator: on a
host with a TPU the chip belongs to one process at a time, and on a host
with a TPU-less libtpu an unpinned child hangs probing for plugins.  The
child therefore inherits the test run's ``JAX_PLATFORMS``, and ``cpu`` when
the run set none.  Program code never pins the platform.
"""
from __future__ import annotations

import os


def jax_subprocess_env(extra: dict | None = None) -> dict:
    """Minimal environment for a test subprocess that will ``import jax``."""
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "PYTHONPATH": os.environ.get("PYTHONPATH", "src"),
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
    }
    for key in ("HOME", "TMPDIR", "XDG_CACHE_HOME"):
        if key in os.environ:
            env[key] = os.environ[key]
    if extra:
        env.update(extra)
    return env
