"""Unified language-model substrate for all ten assigned architectures.

One parameter/forward implementation covers the dense / moe / vlm / audio /
ssm / hybrid families.  Layers are *scanned* (params stacked on a leading
axis) so the lowered HLO stays small enough to compile 512-device meshes on
one CPU host.  Activation/param logical-axis annotations flow through
`repro.distributed.sharding.constrain`.

A configuration with ``layer_types`` (family ``pattern``) is a per-layer
pattern of mixer kinds instead: each layer is ``x += r * mixer(norm(x))``
then ``x += r * mlp(norm(x))``, the mixer Mamba-2 (``"mamba"``) or causal
GQA (``"attention"``).  Each maximal run of one kind is one parameter tree
stacked over its layers (``params["layers"]``, a list in pattern order) and
one ``lax.scan``; the decode cache holds conv and SSM state for a Mamba-2
run and K/V for an attention run, in the same list.

The training step's operations carry fixed ``jax.named_scope`` names,
shared by every family: ``embed``, ``attn``, ``ssm`` (the Mamba-2 mixer),
``mlp`` (the MoE block too), ``norm`` and ``head_loss`` here, in `layers`
and in `mamba2`, ``adamw`` in the optimizer.  They are op metadata only; a
device trace reads them back.

Entry points:
  init_params(cfg, key)            -> (params, logical_axes)
  loss_fn(params, batch, cfg)      -> (scalar loss, metrics)  [train/prefill]
  init_decode_cache(cfg, B, S_max) -> cache pytree (+ axes)
  decode_step(params, cache, tokens, cache_len, cfg) -> (logits, cache)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain, stack_axes

from .layers import (
    attention_block, attention_decode, cross_entropy, embed, init_attention,
    init_embedding, init_mlp, init_rms, mlp_block, rms_norm, _init,
)
from .mamba2 import (
    CONV_K, init_mamba2, mamba2_block, mamba2_decode,
)
from .moe import init_moe, moe_block


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack(inits):
    """Stack a list of identical pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *inits)


def _init_block(cfg: ArchConfig, key):
    """One transformer/moe/ssm block's params + logical axes."""
    ks = jax.random.split(key, 4)
    dt = cfg.jdtype
    if cfg.family == "ssm" or cfg.family == "hybrid":
        p, a = init_mamba2(ks[0], cfg.d_model, cfg.ssm_state, cfg.ssm_headdim,
                           cfg.ssm_expand, dt)
        n, na = init_rms(cfg.d_model)
        return {"mixer": p, "norm": n}, {"mixer": a, "norm": na}
    params: dict = {}
    axes: dict = {}
    params["attn"], axes["attn"] = init_attention(
        ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.qk_norm, dt)
    params["norm1"], axes["norm1"] = init_rms(cfg.d_model)
    params["norm2"], axes["norm2"] = init_rms(cfg.d_model)
    if cfg.family == "moe":
        params["moe"], axes["moe"] = init_moe(
            ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts, dt)
    else:
        params["mlp"], axes["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dt)
    return params, axes


def _init_pattern_layer(cfg: ArchConfig, kind: str, key):
    """One layer of a pattern: its mixer, MLP and two norms."""
    ks = jax.random.split(key, 2)
    dt = cfg.jdtype
    if kind == "mamba":
        mixer = init_mamba2(ks[0], cfg.d_model, cfg.ssm_state, cfg.ssm_headdim,
                            cfg.ssm_expand, dt, cfg.ssm_conv_bias)
    elif kind == "attention":
        mixer = init_attention(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.hd, cfg.qk_norm, dt)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    mlp = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dt)
    n1, n2 = init_rms(cfg.d_model), init_rms(cfg.d_model)
    parts = {"mixer": mixer, "mlp": mlp, "norm1": n1, "norm2": n2}
    return ({k: v[0] for k, v in parts.items()},
            {k: v[1] for k, v in parts.items()})


def _init_pattern(cfg: ArchConfig, key):
    """Per run of ``cfg.runs()``, its layers' parameters stacked."""
    keys = iter(jax.random.split(key, cfg.n_layers))
    params, axes = [], []
    for kind, n in cfg.runs():
        layers = [_init_pattern_layer(cfg, kind, next(keys)) for _ in range(n)]
        params.append(_stack([p for p, _ in layers]))
        axes.append(stack_axes(layers[0][1]))
    return params, axes


def init_params(cfg: ArchConfig, key):
    ks = jax.random.split(key, 8)
    dt = cfg.jdtype
    params: dict = {}
    axes: dict = {}

    if cfg.layer_types:
        params["embed"], axes["embed"] = init_embedding(ks[0], cfg.vocab,
                                                        cfg.d_model, dt)
        if not cfg.tie_embeddings:
            params["lm_head"] = _init(ks[1], (cfg.d_model, cfg.vocab),
                                      1.0 / math.sqrt(cfg.d_model), dt)
            axes["lm_head"] = ("embed", "vocab")
        params["layers"], axes["layers"] = _init_pattern(cfg, ks[2])
        params["final_norm"], axes["final_norm"] = init_rms(cfg.d_model)
        return params, axes

    if cfg.family == "audio":
        K = cfg.n_codebooks
        tabs = [init_embedding(k, cfg.vocab, cfg.d_model, dt)[0]
                for k in jax.random.split(ks[0], K)]
        params["embed"] = jnp.stack(tabs)
        axes["embed"] = (None, "vocab", "embed")
        params["lm_head"] = _init(ks[1], (cfg.d_model, K * cfg.vocab),
                                  1.0 / math.sqrt(cfg.d_model), dt)
        axes["lm_head"] = ("embed", "vocab")
    else:
        params["embed"], axes["embed"] = init_embedding(ks[0], cfg.vocab,
                                                        cfg.d_model, dt)
        params["lm_head"] = _init(ks[1], (cfg.d_model, cfg.vocab),
                                  1.0 / math.sqrt(cfg.d_model), dt)
        axes["lm_head"] = ("embed", "vocab")

    blocks = [_init_block(cfg, k) for k in jax.random.split(ks[2], cfg.n_layers)]
    params["layers"] = _stack([b[0] for b in blocks])
    axes["layers"] = stack_axes(blocks[0][1])

    if cfg.family == "hybrid":
        # one shared full transformer block (attention + MLP), re-entrant
        sp: dict = {}
        sa: dict = {}
        sp["attn"], sa["attn"] = init_attention(
            ks[3], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.qk_norm, dt)
        sp["mlp"], sa["mlp"] = init_mlp(ks[4], cfg.d_model, cfg.d_ff, dt)
        sp["norm1"], sa["norm1"] = init_rms(cfg.d_model)
        sp["norm2"], sa["norm2"] = init_rms(cfg.d_model)
        params["shared_attn"] = sp
        axes["shared_attn"] = sa

    params["final_norm"], axes["final_norm"] = init_rms(cfg.d_model)
    return params, axes


# ---------------------------------------------------------------------------
# blocks (forward)
# ---------------------------------------------------------------------------

@jax.named_scope("norm")
def _norm(x, weight, eps):
    """A block's or the final RMS norm (attention's q/k norms stay under
    ``attn``)."""
    return rms_norm(x, weight, eps)


def _dense_block(cfg: ArchConfig, p, x, positions):
    h = _norm(x, p["norm1"], cfg.norm_eps)
    h = attention_block(p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, positions=positions,
                        qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
                        norm_eps=cfg.norm_eps, q_block=cfg.q_block)
    x = x + h
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    h = _norm(x, p["norm2"], cfg.norm_eps)
    if cfg.family == "moe":
        with jax.named_scope("mlp"):
            h, aux = moe_block(p["moe"], h, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor,
                               groups=cfg.moe_groups)
    else:
        h, aux = mlp_block(p["mlp"], h), jnp.zeros((), jnp.float32)
    x = x + h
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    return x, aux


def _ssm_block(cfg: ArchConfig, p, x):
    h = _norm(x, p["norm"], cfg.norm_eps)
    h = mamba2_block(p["mixer"], h, d_state=cfg.ssm_state,
                     headdim=cfg.ssm_headdim, expand=cfg.ssm_expand,
                     chunk=cfg.ssm_chunk, norm_eps=cfg.norm_eps)
    x = x + h
    return constrain(x, ("act_batch", "act_seq", "act_embed"))


def _shared_block(cfg: ArchConfig, p, x, positions):
    h = _norm(x, p["norm1"], cfg.norm_eps)
    h = attention_block(p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, positions=positions,
                        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                        q_block=cfg.q_block)
    x = x + h
    h = _norm(x, p["norm2"], cfg.norm_eps)
    x = x + mlp_block(p["mlp"], h)
    return constrain(x, ("act_batch", "act_seq", "act_embed"))


def _pattern_layer(cfg: ArchConfig, kind: str, positions, x, p):
    """One layer of a pattern: the mixer, then the MLP, each on its own
    norm and added back scaled by ``residual_multiplier``."""
    r = cfg.residual_multiplier
    h = _norm(x, p["norm1"], cfg.norm_eps)
    if kind == "mamba":
        h = mamba2_block(p["mixer"], h, d_state=cfg.ssm_state,
                         headdim=cfg.ssm_headdim, expand=cfg.ssm_expand,
                         chunk=cfg.ssm_chunk, norm_eps=cfg.norm_eps)
    else:
        h = attention_block(p["mixer"], h, n_heads=cfg.n_heads,
                            n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                            positions=positions, qk_norm=cfg.qk_norm,
                            rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                            q_block=cfg.q_block, rope=cfg.rope,
                            scale=cfg.score_scale)
    x = constrain(x + r * h, ("act_batch", "act_seq", "act_embed"))
    h = _norm(x, p["norm2"], cfg.norm_eps)
    x = x + r * mlp_block(p["mlp"], h)
    return constrain(x, ("act_batch", "act_seq", "act_embed"))


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------

def forward(params, cfg: ArchConfig, x, positions):
    """Backbone over embedded inputs x: (B, S, D) -> (B, S, D)."""
    if cfg.layer_types:
        for (kind, _), run in zip(cfg.runs(), params["layers"]):
            blk = _maybe_remat(partial(_pattern_layer, cfg, kind, positions),
                               cfg)
            x, _ = jax.lax.scan(lambda xx, p: (blk(xx, p), None), x, run)
        return (_norm(x, params["final_norm"], cfg.norm_eps),
                jnp.zeros((), jnp.float32))
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        blk = _maybe_remat(
            lambda xx, p: (_dense_block(cfg, p, xx, positions)), cfg)

        def body(carry, p):
            xx, aux = carry
            xx, a = blk(xx, p)
            return (xx, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
    elif cfg.family == "ssm":
        blk = _maybe_remat(lambda xx, p: _ssm_block(cfg, p, xx), cfg)
        x, _ = jax.lax.scan(lambda xx, p: (blk(xx, p), None), x,
                            params["layers"])
        aux = jnp.zeros((), jnp.float32)
    elif cfg.family == "hybrid":
        aux = jnp.zeros((), jnp.float32)
        period = cfg.attn_every
        groups = cfg.n_layers // period
        head_n = groups * period
        head = jax.tree.map(
            lambda a: a[:head_n].reshape(groups, period, *a.shape[1:]),
            params["layers"])
        tail = jax.tree.map(lambda a: a[head_n:], params["layers"])
        blk = _maybe_remat(lambda xx, p: _ssm_block(cfg, p, xx), cfg)
        shared = _maybe_remat(
            lambda xx, p: _shared_block(cfg, p, xx, positions), cfg)

        def group_body(xx, gp):
            xx, _ = jax.lax.scan(lambda c, p: (blk(c, p), None), xx, gp)
            xx = shared(xx, params["shared_attn"])
            return xx, None

        x, _ = jax.lax.scan(group_body, x, head)
        if cfg.n_layers - head_n:
            x, _ = jax.lax.scan(lambda c, p: (blk(c, p), None), x, tail)
    else:
        raise ValueError(cfg.family)
    return _norm(x, params["final_norm"], cfg.norm_eps), aux


@jax.named_scope("embed")
def embed_inputs(params, cfg: ArchConfig, batch):
    """Family-specific input embedding.  Returns (x, positions, label_info)."""
    if cfg.family == "vlm":
        tok_x = embed(params["embed"], batch["tokens"])
        x = jnp.concatenate([batch["patches"].astype(tok_x.dtype), tok_x], axis=1)
    elif cfg.family == "audio":
        # codes: (B, K, S) -> sum of per-codebook embeddings
        K = cfg.n_codebooks
        x = sum(embed(params["embed"][k], batch["codes"][:, k]) for k in range(K))
    else:
        x = embed(params["embed"], batch["tokens"])
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    return x, positions


def _logits(params, cfg: ArchConfig, h):
    """The LM head's logits of ``h``: through the embedding's transpose
    where the head is tied, divided by ``logits_scaling``.  The division
    scales ``h``, a vocabulary-wide product's narrower side; by a power of
    two, as Granite's 8, it is exact."""
    if cfg.logits_scaling != 1.0:
        h = h * (1.0 / cfg.logits_scaling)
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


@jax.named_scope("head_loss")
def _head_loss(params, cfg: ArchConfig, h, labels):
    """Next-token cross-entropy of the LM head's logits."""
    if cfg.family == "audio":
        B, S, D = h.shape
        logits = (h @ params["lm_head"]).reshape(B, S, cfg.n_codebooks, cfg.vocab)
        logits = logits[:, :-1]
        lbl = labels[:, :, 1:].transpose(0, 2, 1)  # (B,S-1,K)
        return cross_entropy(logits, lbl)
    logits = _logits(params, cfg, h)
    logits = constrain(logits, ("act_batch", "act_seq", "act_vocab"))
    return cross_entropy(logits[:, :-1], labels[:, 1:])


def loss_fn(params, batch, cfg: ArchConfig):
    """Causal LM loss over the batch.  Returns (loss, metrics)."""
    x, positions = embed_inputs(params, cfg, batch)
    h, aux = forward(params, cfg, x, positions)
    loss = _head_loss(params, cfg, h, batch["labels"])
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# decode path (serve_step)
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ArchConfig, batch: int, max_len: int):
    """Cache pytree + logical axes for one-token decoding."""
    dt = cfg.jdtype
    kv_dt = getattr(jnp, cfg.kv_dtype) if cfg.kv_dtype else dt
    L = cfg.n_layers
    if cfg.layer_types:
        return _pattern_cache(cfg, batch, max_len, dt, kv_dt)
    if cfg.family == "ssm" or cfg.family == "hybrid":
        d_inner = cfg.ssm_expand * cfg.d_model
        nheads = d_inner // cfg.ssm_headdim
        conv_c = d_inner + 2 * cfg.ssm_state
        cache = {
            "conv": jnp.zeros((L, batch, CONV_K - 1, conv_c), dt),
            "ssm": jnp.zeros((L, batch, nheads, cfg.ssm_headdim, cfg.ssm_state), dt),
        }
        axes = {
            "conv": ("layers", "act_batch", None, "act_ffn"),
            "ssm": ("layers", "act_batch", None, None, None),
        }
        if cfg.family == "hybrid":
            n_shared = cfg.n_layers // cfg.attn_every
            cache["k"] = jnp.zeros((n_shared, batch, max_len, cfg.n_kv_heads, cfg.hd), dt)
            cache["v"] = jnp.zeros_like(cache["k"])
            axes["k"] = (None, "act_batch", None, "act_kv", "act_hd")
            axes["v"] = axes["k"]
        return cache, axes
    cache = {
        "k": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.hd), kv_dt),
        "v": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.hd), kv_dt),
    }
    axes = {"k": ("layers", "act_batch", None, "act_kv", "act_hd"),
            "v": ("layers", "act_batch", None, "act_kv", "act_hd")}
    return cache, axes


def _pattern_cache(cfg: ArchConfig, batch: int, max_len: int, dt, kv_dt):
    """Per run of ``cfg.runs()``: a Mamba-2 run's conv window and float32
    SSM state, or an attention run's K and V, stacked over its layers."""
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_headdim
    caches, axes = [], []
    for kind, n in cfg.runs():
        if kind == "mamba":
            caches.append({
                "conv": jnp.zeros((n, batch, CONV_K - 1,
                                   d_inner + 2 * cfg.ssm_state), dt),
                "ssm": jnp.zeros((n, batch, nheads, cfg.ssm_headdim,
                                  cfg.ssm_state), jnp.float32)})
            axes.append({"conv": ("layers", "act_batch", None, "act_ffn"),
                         "ssm": ("layers", "act_batch", None, None, None)})
        else:
            kv = ("layers", "act_batch", None, "act_kv", "act_hd")
            caches.append({
                "k": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, cfg.hd),
                               kv_dt),
                "v": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, cfg.hd),
                               kv_dt)})
            axes.append({"k": kv, "v": kv})
    return {"layers": caches}, {"layers": axes}


def _pattern_decode(params, cache, x, cache_len, cfg: ArchConfig):
    """One token through a pattern's runs, each run's cache scanned with
    its layers.  Returns (x, new cache)."""
    r = cfg.residual_multiplier

    def mlp(xx, p):
        return xx + r * mlp_block(p["mlp"], rms_norm(xx, p["norm2"],
                                                     cfg.norm_eps))

    def mamba(xx, layer):
        p, conv, ssm = layer
        h = rms_norm(xx, p["norm1"], cfg.norm_eps)
        h, new = mamba2_decode(p["mixer"], h, {"conv": conv, "ssm": ssm},
                               d_state=cfg.ssm_state, headdim=cfg.ssm_headdim,
                               expand=cfg.ssm_expand, norm_eps=cfg.norm_eps)
        return mlp(xx + r * h, p), (new["conv"], new["ssm"])

    def attention(xx, layer):
        p, ck, cv = layer
        h = rms_norm(xx, p["norm1"], cfg.norm_eps)
        h, ck, cv = attention_decode(
            p["mixer"], h, ck, cv, cache_len, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.hd, qk_norm=cfg.qk_norm,
            rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, rope=cfg.rope,
            scale=cfg.score_scale)
        return mlp(xx + r * h, p), (ck, cv)

    new = []
    for (kind, _), run, c in zip(cfg.runs(), params["layers"],
                                 cache["layers"]):
        keys = ("conv", "ssm") if kind == "mamba" else ("k", "v")
        x, out = jax.lax.scan(mamba if kind == "mamba" else attention, x,
                              (run, *(c[k] for k in keys)))
        new.append(dict(zip(keys, out)))
    return x, {"layers": new}


def decode_step(params, cache, tokens, cache_len, cfg: ArchConfig):
    """One-token decode.  tokens: (B,1) int32 (audio: (B,K,1)).

    Returns (logits, new_cache)."""
    if cfg.layer_types:
        x = embed(params["embed"], tokens)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        x, new_cache = _pattern_decode(params, cache, x, cache_len, cfg)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return _logits(params, cfg, x), new_cache
    if cfg.family == "audio":
        K = cfg.n_codebooks
        x = sum(embed(params["embed"][k], tokens[:, k]) for k in range(K))
    elif cfg.family == "vlm":
        x = embed(params["embed"], tokens)
    else:
        x = embed(params["embed"], tokens)
    x = constrain(x, ("act_batch", None, "act_embed"))

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        def body(xx, layer):
            p, ck, cv = layer
            h = rms_norm(xx, p["norm1"], cfg.norm_eps)
            h, ck, cv = attention_decode(
                p["attn"], h, ck, cv, cache_len, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, head_dim=cfg.hd, qk_norm=cfg.qk_norm,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)
            xx = xx + h
            h = rms_norm(xx, p["norm2"], cfg.norm_eps)
            if cfg.family == "moe":
                h, _ = moe_block(p["moe"], h, top_k=cfg.top_k,
                                 capacity_factor=cfg.capacity_factor)
            else:
                h = mlp_block(p["mlp"], h)
            xx = xx + h
            return xx, (ck, cv)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": k_new, "v": v_new}
    elif cfg.family == "ssm":
        def body(xx, layer):
            p, conv, ssm = layer
            h = rms_norm(xx, p["norm"], cfg.norm_eps)
            h, new = mamba2_decode(p["mixer"], h, {"conv": conv, "ssm": ssm},
                                   d_state=cfg.ssm_state, headdim=cfg.ssm_headdim,
                                   expand=cfg.ssm_expand, norm_eps=cfg.norm_eps)
            return xx + h, (new["conv"], new["ssm"])

        x, (conv_new, ssm_new) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["ssm"]))
        new_cache = {"conv": conv_new, "ssm": ssm_new}
    elif cfg.family == "hybrid":
        period = cfg.attn_every
        groups = cfg.n_layers // period
        head_n = groups * period

        def ssm_body(xx, layer):
            p, conv, ssm = layer
            h = rms_norm(xx, p["norm"], cfg.norm_eps)
            h, new = mamba2_decode(p["mixer"], h, {"conv": conv, "ssm": ssm},
                                   d_state=cfg.ssm_state, headdim=cfg.ssm_headdim,
                                   expand=cfg.ssm_expand, norm_eps=cfg.norm_eps)
            return xx + h, (new["conv"], new["ssm"])

        take = lambda a, lo, n: jax.tree.map(lambda t: t[lo:lo + n], a)
        convs, ssms = [], []
        ks, vs = [], []
        for g in range(groups):
            layer = (take(params["layers"], g * period, period),
                     take(cache["conv"], g * period, period),
                     take(cache["ssm"], g * period, period))
            x, (c_new, s_new) = jax.lax.scan(ssm_body, x, layer)
            convs.append(c_new)
            ssms.append(s_new)
            sp = params["shared_attn"]
            h = rms_norm(x, sp["norm1"], cfg.norm_eps)
            h, ck, cv = attention_decode(
                sp["attn"], h, cache["k"][g], cache["v"][g], cache_len,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)
            x = x + h
            h = rms_norm(x, sp["norm2"], cfg.norm_eps)
            x = x + mlp_block(sp["mlp"], h)
            ks.append(ck)
            vs.append(cv)
        if cfg.n_layers - head_n:
            layer = (take(params["layers"], head_n, cfg.n_layers - head_n),
                     take(cache["conv"], head_n, cfg.n_layers - head_n),
                     take(cache["ssm"], head_n, cfg.n_layers - head_n))
            x, (c_new, s_new) = jax.lax.scan(ssm_body, x, layer)
            convs.append(c_new)
            ssms.append(s_new)
        new_cache = {
            "conv": jnp.concatenate(convs), "ssm": jnp.concatenate(ssms),
            "k": jnp.stack(ks), "v": jnp.stack(vs),
        }
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    if cfg.family == "audio":
        B = x.shape[0]
        logits = logits.reshape(B, 1, cfg.n_codebooks, cfg.vocab)
    return logits, new_cache
