"""Sharded train / prefill / decode step builders.

`build_train_step(cfg, mesh)` returns (step_fn, state_shardings) where
step_fn(state, batch) -> (state, metrics) is ready for jax.jit with the
returned shardings.  The same builders drive the real trainer, the examples,
and the 512-device dry-run (which only lowers + compiles them).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import (
    ShardingRules, default_rules, logical_to_spec, param_shardings, use_rules,
)
from repro.models.lm import decode_step, init_decode_cache, init_params, loss_fn
from repro.optim.adamw import (
    AdamWConfig, adamw_update, init_opt_state, opt_state_axes,
)
from repro.optim.compression import CompressionConfig, compress_gradients


def batch_shardings(rules: ShardingRules, batch_axes: dict):
    return {k: NamedSharding(rules.mesh, logical_to_spec(rules, v))
            for k, v in batch_axes.items()}


def batch_axes_for(cfg: ArchConfig, kind: str) -> dict:
    if kind == "decode":
        ax = {"tokens": ("act_batch", None, None) if cfg.family == "audio"
              else ("act_batch", None),
              "cache_len": ()}
        return ax
    if cfg.family == "vlm":
        return {"tokens": ("act_batch", "act_seq"),
                "patches": ("act_batch", "act_seq", None),
                "labels": ("act_batch", "act_seq")}
    if cfg.family == "audio":
        return {"codes": ("act_batch", None, "act_seq"),
                "labels": ("act_batch", None, "act_seq")}
    return {"tokens": ("act_batch", "act_seq"),
            "labels": ("act_batch", "act_seq")}


def make_train_state(cfg: ArchConfig, key):
    params, axes = init_params(cfg, key)
    opt = init_opt_state(params)
    return {"params": params, "opt": opt}, {"params": axes,
                                            "opt": opt_state_axes(axes)}


def state_shardings(rules: ShardingRules, state_axes):
    return param_shardings(rules, state_axes)


def build_train_step(cfg: ArchConfig, rules: ShardingRules,
                     opt_cfg: AdamWConfig | None = None,
                     compression: CompressionConfig | None = None,
                     n_micro: int = 1):
    """Returns step(state, batch) -> (state, metrics), pure & jit-ready.

    ``n_micro > 1`` enables gradient accumulation: the global batch is split
    into microbatches scanned sequentially, so activation memory scales with
    the *microbatch* while arithmetic intensity per chip is unchanged.  This
    is what lets the large dense/moe cells fit 16GB HBM at global batch 256.
    The accumulation buffer is float32.
    """
    opt_cfg = opt_cfg or AdamWConfig()

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)

    def step(state, batch):
        with use_rules(rules):
            params = state["params"]
            if n_micro > 1:
                micro = jax.tree.map(
                    lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                        *x.shape[1:]),
                    batch)

                def acc_fn(acc, mb):
                    (loss, metrics), g = grads_of(params, mb)
                    gacc, lacc, aacc = acc
                    gacc = jax.tree.map(
                        lambda a, b: a + (b / n_micro).astype(a.dtype), gacc, g)
                    return (gacc, lacc + loss / n_micro,
                            aacc + metrics["aux_loss"] / n_micro), None

                zero = (jax.tree.map(
                            lambda p: jnp.zeros(p.shape, jnp.float32), params),
                        jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
                (grads, loss, aux), _ = jax.lax.scan(acc_fn, zero, micro)
                metrics = {"loss": loss, "aux_loss": aux}
            else:
                (loss, metrics), grads = grads_of(params, batch)
            if compression is not None and compression.enabled:
                grads, state_err, cstats = compress_gradients(
                    grads, state.get("err"), compression)
                metrics.update(cstats)
            else:
                state_err = state.get("err")
            new_params, new_opt, opt_metrics = adamw_update(
                opt_cfg, params, grads, state["opt"])
            metrics.update(opt_metrics)
            metrics["loss_total"] = loss
            out = {"params": new_params, "opt": new_opt}
            if state_err is not None:
                out["err"] = state_err
            return out, metrics

    return step


def build_eval_step(cfg: ArchConfig, rules: ShardingRules):
    def step(params, batch):
        with use_rules(rules):
            loss, metrics = loss_fn(params, batch, cfg)
            return metrics

    return step


def build_prefill_step(cfg: ArchConfig, rules: ShardingRules, n_micro: int = 1):
    """Forward-only step (inference prefill): returns logits stats + loss.

    ``n_micro`` scans the request batch in chunks so the 32k-token MoE
    dispatch working set stays inside HBM."""
    def step(params, batch):
        with use_rules(rules):
            if n_micro > 1:
                micro = jax.tree.map(
                    lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                        *x.shape[1:]),
                    batch)

                def one(acc, mb):
                    loss, _ = loss_fn(params, mb, cfg)
                    return acc + loss / n_micro, None

                loss, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), micro)
                return {"loss": loss}
            loss, metrics = loss_fn(params, batch, cfg)
            return {"loss": loss, **metrics}

    return step


def build_decode_step(cfg: ArchConfig, rules: ShardingRules):
    """serve_step: one new token against a seq-deep KV/state cache."""
    def step(params, cache, tokens, cache_len):
        with use_rules(rules):
            logits, new_cache = decode_step(params, cache, tokens, cache_len, cfg)
            next_tok = jnp.argmax(logits[..., -1, :] if cfg.family != "audio"
                                  else logits[:, -1], axis=-1)
            return next_tok, new_cache

    return step
