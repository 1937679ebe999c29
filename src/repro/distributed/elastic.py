"""Elastic scaling: rebuild the mesh from surviving devices and reshard.

On a real fleet, losing a slice means restarting the job on fewer hosts; the
recovery path is exactly what `reshard_state` implements — load the last
checkpoint (host arrays) and `device_put` with shardings derived from the
*new* mesh.  Because every sharding in this codebase is derived from logical
rules + concrete shapes (`shardings_for`), nothing else changes: the same
step builder compiles for the new topology.
"""
from __future__ import annotations

import jax

from repro.distributed.sharding import default_rules, shardings_for
from repro.launch.mesh import make_host_mesh


def degraded_mesh(devices=None, model: int | None = None):
    """Largest (data, model) mesh from the given devices (default: all)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model is None:
        model = 1
        for m in (16, 8, 4, 2):
            if n % m == 0 and m <= n:
                model = m
                break
    return make_host_mesh(model, devices)


def reshard_state(state, axes_tree, new_mesh, sequence_parallel: bool = False):
    """Re-place a host-loaded (or device) state onto a new mesh."""
    rules = default_rules(new_mesh, sequence_parallel=sequence_parallel)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    sh = shardings_for(rules, axes_tree, shapes)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh), rules
