"""Blocked causal attention (FlashAttention-style online softmax) for TPU.

Grid (bh, qi, ki) with the KV axis innermost ('arbitrary'): running max /
sum / accumulator tiles live in VMEM scratch across KV steps, so HBM traffic
is one pass over Q, K, V and one write of O — the attention analogue of the
LTRF working-set guarantee (everything the inner loop touches is
VMEM-resident; K/V tiles stream through the pipeline's buffer slots).

GQA is handled in the index map: query head h reads kv head h // (H // KV).
Causality is enforced per-tile with an index mask (fully-masked tiles still
execute; the wrapper chooses block sizes so they are a small fraction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, bq: int, bk: int, n_k: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq,)
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,            # (BH, S, d)   (batch*heads flattened)
    k: jax.Array,            # (BKV, S, d)
    v: jax.Array,
    *,
    group: int,              # H // KV (query heads per kv head)
    bq: int = 512,
    bk: int = 512,
    causal: bool = True,
    interpret: bool = False,
) -> jax.Array:
    BH, S, d = q.shape
    assert S % bq == 0 and S % bk == 0
    n_k = S // bk
    scale = 1.0 / (d ** 0.5)

    grid = (BH, S // bq, n_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk,
                          n_k=n_k, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
