"""Entry-point plumbing: mesh axis types, the compile-cache rule, and
`chip_smoke.py` refusing to report a result without a TPU."""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

import jax
from jax.sharding import AxisType

from jax_subprocess import jax_subprocess_env
from repro.distributed.elastic import degraded_mesh
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_meshes_have_auto_axes():
    for mesh in (make_host_mesh(), degraded_mesh(jax.devices()[:1], model=1)):
        assert mesh.axis_names == ("data", "model")
        assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # sets nothing


def test_compile_cache_defaults_into_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_fails_without_a_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       cwd=ROOT, env=jax_subprocess_env())
    assert r.returncode != 0, r.stdout + r.stderr
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "device" not in last
