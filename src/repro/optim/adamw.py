"""AdamW with decoupled weight decay, global-norm clipping and fp32 master
state — implemented directly in JAX (no optax dependency).

State mirrors the parameter pytree (so FSDP sharding rules apply to optimizer
state automatically) plus a scalar step count.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def lr_schedule(cfg: AdamWConfig, step):
    """Linear warmup + cosine decay."""
    step = step.astype(jnp.float32) if hasattr(step, "astype") else jnp.float32(step)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    prog = (step - cfg.warmup_steps) / jnp.maximum(
        cfg.total_steps - cfg.warmup_steps, 1)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    decayed = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, decayed)


def init_opt_state(params):
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {
        "mu": zeros,
        "nu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "step": jnp.zeros((), jnp.int32),
    }


def opt_state_axes(param_axes):
    """Logical axes for the optimizer state (mirrors params)."""
    return {
        "mu": param_axes,
        "nu": param_axes,
        "step": (),
    }


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


@jax.named_scope("adamw")
def adamw_update(cfg: AdamWConfig, params, grads, state):
    """Returns (new_params, new_state, metrics).  Runs under the named
    scope ``adamw``, the global norm included."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    lr = lr_schedule(cfg, step)

    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd_leaf(p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu = cfg.b1 * mu + (1 - cfg.b1) * g
        nu = cfg.b2 * nu + (1 - cfg.b2) * g * g
        mhat = mu / b1c
        vhat = nu / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), mu, nu

    # NOTE: a lax.map-over-layers variant was tried to shrink the fp32 update
    # temporaries (~0.66GB/leaf at 132B) but REGRESSED: scan outputs cannot
    # alias their inputs, so the optimizer state double-buffers (+4GB >> the
    # temp saving).  Measured in EXPERIMENTS.md §Perf (dbrx iter H8).
    upd = upd_leaf

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(state["mu"])
    flat_nu = treedef.flatten_up_to(state["nu"])
    out = [upd(p, g, m, n) for p, g, m, n in zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_state = {
        "mu": treedef.unflatten([o[1] for o in out]),
        "nu": treedef.unflatten([o[2] for o in out]),
        "step": step,
    }
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
