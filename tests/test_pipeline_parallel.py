"""Pipeline-parallel correctness: GPipe schedule == sequential oracle.

Runs in a subprocess with XLA_FLAGS forcing 4 host devices so the pipeline
axis is real (the main test process keeps 1 device)."""
import subprocess
import sys
import textwrap

from jax_subprocess import jax_subprocess_env

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline_parallel import (
        pipeline_forward, sequential_reference)

    mesh = jax.make_mesh((4,), ("stage",))
    D = 16

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    k = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(k, (4, D, D)) * 0.5,
        "b": jnp.linspace(-1, 1, 4)[:, None] * jnp.ones((4, D)),
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 8, D))  # 6 micro x 8 x D

    got = pipeline_forward(stage_fn, params, x, mesh)
    want = sequential_reference(stage_fn, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("PIPELINE_OK")
""")


def test_gpipe_matches_sequential():
    # jax_subprocess_env pins JAX_PLATFORMS: without it, jax probes for
    # accelerator plugins, which hangs on hosts with a TPU-less libtpu.
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300, env=jax_subprocess_env())
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
