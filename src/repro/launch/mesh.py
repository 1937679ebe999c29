"""Mesh construction.

Functions, not module-level constants, so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips
(one v5e pod).  Multi-pod: (pod=2, data=16, model=16) = 512 chips; the
leading 'pod' axis carries only data parallelism (gradient all-reduce over
DCN), matching how real multi-pod training lays out traffic.

Every mesh in the repo is built here with ``Auto`` axes: the model code
places intermediates with ``with_sharding_constraint`` over logical-axis
rules (`repro.distributed.sharding`), which only ``Auto`` axes accept.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, devices=None):
    """(data, model) mesh over the given local devices (default: all)."""
    devices = list(devices if devices is not None else jax.devices())
    data = len(devices) // model
    return Mesh(np.array(devices[: data * model]).reshape(data, model),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
