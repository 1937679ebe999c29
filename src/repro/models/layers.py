"""Shared neural-net layers (pure functional JAX, no framework deps).

Params are plain pytrees of jnp arrays.  Every init function returns
(params, logical_axes) where logical_axes mirrors the params pytree with
tuples of logical axis names consumed by repro.distributed.sharding.

The attention and MLP blocks run under the ``jax.named_scope``s ``attn``
and ``mlp``: op metadata only, which a device trace reads back to put each
operation (forward, remat recompute and backward alike) down to its block.

Causal self-attention takes the fused flash kernel
(``kernels/flash_attention``) where its preconditions hold and the XLA
q-block scan (``causal_attention``) elsewhere; ``ATTN_STATS`` counts which
path each call took on the platform the step was lowered for
(`repro.models.paths`).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import active_rules
from repro.kernels.flash_attention.kernel import causal_tiles
from repro.kernels.flash_attention.ops import flash_attention

from . import paths


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(dt)


def init_rms(d, dtype=jnp.float32):
    return jnp.ones((d,), dtype=dtype), ("embed",)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq)"""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), dtype=jnp.float32)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, causal, memory-efficient q-blocked form)
# ---------------------------------------------------------------------------

def init_attention(key, d_model, n_heads, n_kv, head_dim, qk_norm, dtype):
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d_model)
    params = {
        "wq": _init(ks[0], (d_model, n_heads * head_dim), s, dtype),
        "wk": _init(ks[1], (d_model, n_kv * head_dim), s, dtype),
        "wv": _init(ks[2], (d_model, n_kv * head_dim), s, dtype),
        "wo": _init(ks[3], (n_heads * head_dim, d_model), s / math.sqrt(2), dtype),
    }
    axes = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv"),
        "wv": ("embed", "kv"),
        "wo": ("heads", "embed"),
    }
    if qk_norm:
        params["q_norm"] = jnp.ones((head_dim,), dtype=jnp.float32)
        params["k_norm"] = jnp.ones((head_dim,), dtype=jnp.float32)
        axes["q_norm"] = (None,)
        axes["k_norm"] = (None,)
    return params, axes


def _qkv(params, x, cfg_heads, cfg_kv, head_dim, positions, qk_norm, rope_theta,
         norm_eps, rope=True):
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, cfg_heads, head_dim)
    k = (x @ params["wk"]).reshape(B, S, cfg_kv, head_dim)
    v = (x @ params["wv"]).reshape(B, S, cfg_kv, head_dim)
    if qk_norm:
        q = rms_norm(q, params["q_norm"], norm_eps)
        k = rms_norm(k, params["k_norm"], norm_eps)
    if rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def _repeat_kv(k, n_heads):
    """(B,S,kv,hd) -> (B,S,H,hd) by repeating groups."""
    B, S, kv, hd = k.shape
    rep = n_heads // kv if n_heads % kv == 0 else -1
    if rep == -1:  # uneven GQA (e.g. 40q/10kv is even; guard anyway)
        rep = -(-n_heads // kv)
        k = jnp.repeat(k, rep, axis=2)[:, :, :n_heads]
        return k
    return jnp.repeat(k, rep, axis=2)


def causal_attention(q, k, v, q_block: int = 512, q_offset=None, scale=None):
    """Memory-efficient causal attention.

    q: (B,Sq,H,hd), k/v: (B,Skv,KV,hd).  Scans over q blocks so peak memory is
    O(Sq_block x Skv) rather than O(Sq x Skv).  ``q_offset`` shifts query
    positions (for decode, q_offset = Skv - Sq).  Scores are scaled by
    ``scale``, ``hd ** -0.5`` when None.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    offset = Skv - Sq if q_offset is None else q_offset

    kT = k.transpose(0, 2, 3, 1)  # (B,H,hd,Skv)
    vT = v.transpose(0, 2, 1, 3)  # (B,H,Skv,hd)
    kv_pos = jnp.arange(Skv)

    q_block = min(q_block, Sq)
    nblk = -(-Sq // q_block)
    pad = nblk * q_block - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    qb = qp.reshape(B, nblk, q_block, H, hd).transpose(1, 0, 3, 2, 4)  # (nblk,B,H,qb,hd)

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def one_q_block(blk_idx, qblk):
        # rematerialized per block: backward never holds more than one
        # (q_block x Skv) logits/softmax tile in memory
        qpos = blk_idx * q_block + jnp.arange(q_block) + offset
        logits = jnp.einsum("bhqd,bhdk->bhqk", qblk.astype(jnp.float32),
                            kT.astype(jnp.float32)) * scale
        mask = kv_pos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vT.astype(jnp.float32))

    def one_block(carry, inp):
        blk_idx, qblk = inp
        return carry, one_q_block(blk_idx, qblk)

    _, outs = jax.lax.scan(one_block, None, (jnp.arange(nblk), qb))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, nblk * q_block, H, hd)
    if pad:
        out = out[:, :Sq]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# path selection: the fused flash kernel where its preconditions hold
# ---------------------------------------------------------------------------

# Which path each self-attention call took (`repro.models.paths`):
# ``kernel_calls`` and ``xla_calls``, and the forward kernel's (query block,
# key block) tiles over every batch row and head, run or skipped by
# causality.
ATTN_STATS = paths.counter("attn", "tiles_run", "tiles_skipped")

FLASH_BLOCKS = (512, 256, 128)


def reset_attn_stats() -> dict:
    return paths.reset(ATTN_STATS)


def flash_block(seq: int, head_dim: int, n_heads: int, n_kv: int):
    """The flash kernel's square tile for causal self-attention over
    ``seq`` positions, or None where only the XLA path may run.

    The kernel's blocks must tile the (8, 128) layout: the sequence in
    multiples of 128, and the head width too, or 64, where a block spans
    the whole last dimension (its lane-broadcast row statistics take a
    width under 128 whole).  GQA maps query head h to kv head
    h // group, so the kv heads must divide the query heads.  The kernel is
    not partitioned across devices, so the active sharding rules' mesh must
    hold one device (or no rules are active)."""
    rules = active_rules()
    if ((head_dim % 128 and head_dim != 64) or n_heads % n_kv
            or (rules is not None and rules.mesh.size > 1)):
        return None
    return next((b for b in FLASH_BLOCKS if seq % b == 0), None)


def _xla_attention(q, k, v, q_block, scale):
    return causal_attention(paths.tag(q, "attn", "xla"), k, v,
                            q_block=q_block, scale=scale)


def _kernel_attention(q, k, v, block, scale):
    B, S, H, _ = q.shape
    run, skipped = causal_tiles(S, block, block)
    q = paths.tag(q, "attn", "kernel", tiles_run=B * H * run,
                  tiles_skipped=B * H * skipped)
    out = flash_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                          bq=block, bk=block, scale=scale)
    return out.transpose(0, 2, 1, 3)


def self_attention(q, k, v, q_block: int = 512, scale=None):
    """Causal self-attention, q: (B,S,H,hd), k/v: (B,S,KV,hd), scores
    scaled by ``scale`` (``hd ** -0.5`` when None).

    On a TPU, the fused flash kernel where ``flash_block`` finds a tile;
    elsewhere, and on other platforms, ``causal_attention``."""
    _, S, H, hd = q.shape
    block = flash_block(S, hd, H, k.shape[2])
    xla = partial(_xla_attention, q_block=q_block, scale=scale)
    if block is None:
        return xla(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, tpu=partial(_kernel_attention, block=block, scale=scale),
        default=xla)


@jax.named_scope("attn")
def attention_block(params, x, *, n_heads, n_kv, head_dim, positions,
                    qk_norm=False, rope_theta=10000.0, norm_eps=1e-5,
                    q_block=512, rope=True, scale=None):
    """Causal GQA self-attention over ``x``: (B, S, D).  ``rope`` off leaves
    the positions out (no position embedding); ``scale`` multiplies the
    scores, ``head_dim ** -0.5`` when None."""
    q, k, v = _qkv(params, x, n_heads, n_kv, head_dim, positions, qk_norm,
                   rope_theta, norm_eps, rope)
    out = self_attention(q, k, v, q_block=q_block, scale=scale)
    B, S, _, _ = out.shape
    return out.reshape(B, S, n_heads * head_dim) @ params["wo"]


def attention_decode(params, x, cache_k, cache_v, cache_len, *, n_heads, n_kv,
                     head_dim, qk_norm=False, rope_theta=10000.0, norm_eps=1e-5,
                     rope=True, scale=None):
    """One-token decode against a (B, S_max, kv, hd) KV cache; ``rope`` and
    ``scale`` as in `attention_block`.

    Returns (out, new_cache_k, new_cache_v).
    """
    B, S, _ = x.shape  # S == 1
    positions = jnp.full((B, S), cache_len, dtype=jnp.int32)
    q, k, v = _qkv(params, x, n_heads, n_kv, head_dim, positions, qk_norm,
                   rope_theta, norm_eps, rope)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), cache_len, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), cache_len, axis=1)
    S_max = cache_k.shape[1]
    kk = _repeat_kv(cache_k, n_heads)
    vv = _repeat_kv(cache_v, n_heads)
    scale = 1.0 / math.sqrt(head_dim) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)) * scale
    mask = jnp.arange(S_max)[None, :] <= cache_len  # current token included
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vv.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(B, S, n_heads * head_dim) @ params["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype):
    ks = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d_model)
    params = {
        "w_gate": _init(ks[0], (d_model, d_ff), s, dtype),
        "w_up": _init(ks[1], (d_model, d_ff), s, dtype),
        "w_down": _init(ks[2], (d_ff, d_model), 1.0 / math.sqrt(d_ff), dtype),
    }
    axes = {"w_gate": ("embed", "ffn"), "w_up": ("embed", "ffn"),
            "w_down": ("ffn", "embed")}
    return params, axes


@jax.named_scope("mlp")
def mlp_block(params, x):
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# embeddings / heads
# ---------------------------------------------------------------------------

def init_embedding(key, vocab, d_model, dtype):
    return _init(key, (vocab, d_model), 1.0, dtype), ("vocab", "embed")


def embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(x, table):
    return x @ table.T


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
