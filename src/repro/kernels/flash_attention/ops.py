"""Jit wrapper for the blocked causal attention kernels (GQA layout glue and
the custom VJP that pairs the forward with its backward kernels)."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import flash_backward, flash_forward
from .ref import attention_ref


def _flat(q, k, v):
    B, H, S, d = q.shape
    KV = k.shape[1]
    return (q.reshape(B * H, S, d), k.reshape(B * KV, S, d),
            v.reshape(B * KV, S, d), H // KV)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attention(q, k, v, bq, bk, causal, interpret, scale):
    return _attention_fwd(q, k, v, bq, bk, causal, interpret, scale)[0]


def _attention_fwd(q, k, v, bq, bk, causal, interpret, scale):
    qf, kf, vf, group = _flat(q, k, v)
    o, lse = flash_forward(qf, kf, vf, group=group, bq=bq, bk=bk,
                           causal=causal, interpret=interpret, scale=scale)
    return o.reshape(q.shape), (q, k, v, o, lse)


def _attention_bwd(bq, bk, causal, interpret, scale, res, do):
    q, k, v, o, lse = res
    qf, kf, vf, group = _flat(q, k, v)
    dq, dk, dv = flash_backward(qf, kf, vf, o, lse, do.reshape(o.shape),
                                group=group, bq=bq, bk=bk, causal=causal,
                                interpret=interpret, scale=scale)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_attention.defvjp(_attention_fwd, _attention_bwd)


@partial(jax.jit, static_argnames=("bq", "bk", "causal", "interpret", "scale"))
def flash_attention(q, k, v, bq: int = 256, bk: int = 256,
                    causal: bool = True, interpret: bool = False,
                    scale: float | None = None):
    """q: (B, H, S, d); k/v: (B, KV, S, d) -> (B, H, S, d); differentiable.
    Scores are scaled by ``scale``, ``d ** -0.5`` when None."""
    B, H, S, d = q.shape
    KV = k.shape[1]
    assert H % KV == 0, (H, KV)
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    return _attention(q, k, v, bq, bk, causal, interpret, scale)


__all__ = ["flash_attention", "attention_ref"]
