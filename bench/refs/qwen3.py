"""Plain float32 reference for training a Qwen3 dense decoder.

Written from the published architecture (Qwen3 technical report,
arXiv:2505.09388; Hugging Face ``Qwen3ForCausalLM``), not from the program:

* token embedding, then ``num_hidden_layers`` pre-norm blocks, a final
  RMSNorm and an output head (untied here: the configuration's one
  departure, see its file);
* RMSNorm ``x / sqrt(mean(x^2) + eps) * w``;
* attention: q, k, v projections without bias, RMSNorm over each head of q
  and k (Qwen3's qk-norm), rotary embedding in the rotate-half form with
  base ``rope_theta``, causal softmax over ``head_dim ** -0.5`` scaled
  scores, grouped-query heads (each kv head serves
  ``num_attention_heads / num_key_value_heads`` query heads), output
  projection;
* MLP: ``down(silu(gate(x)) * up(x))``;
* loss: next-token cross-entropy, the mean over every position but the
  last of each row;
* AdamW as the job's file states it: the gradient clipped to a global norm,
  a learning rate that rises linearly from ``lr / warmup_steps`` at the
  first update and then decays by a cosine to ``min_lr_ratio * lr``,
  bias-corrected moments, decoupled weight decay on every parameter;
  parameters kept in the dtypes the configuration stores them in.

Every matrix product runs in float32 at ``highest`` precision.  To fit one
chip at the cell's size, the layers run under a scan with each layer
recomputed in the backward pass, attention takes blocks of queries, and the
loss takes blocks of rows: the same mathematics, in pieces.

``quant`` says how every matrix product rounds: `exact` gives the
reference, and `fp8` the control, which computes in float8, the precision
below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
# queries per attention block and rows per loss block: sizes of the pieces,
# chosen to fit one chip at the cell's size; they change no result
Q_BLOCK = 256
LOSS_ROWS = 512


class Exact:
    """Matrix products in float32: the reference."""

    @staticmethod
    def operand(x):
        return x

    @staticmethod
    def product(y):
        return y


def _scaled(x, dtype):
    """``x`` rounded to ``dtype`` under one scale per tensor, in float32."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(F32) / scale


@jax.custom_vjp
def _fwd8(x):
    return _scaled(x, jnp.float8_e4m3fn)


_fwd8.defvjp(lambda x: (_fwd8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _bwd8(y):
    return y


_bwd8.defvjp(lambda y: (y, None),
             lambda _, g: (_scaled(g, jnp.float8_e5m2),))


class Fp8:
    """Matrix products as float8 training computes them: operands rounded
    to e4m3 going forward, gradients rounded to e5m2 going back, each
    tensor under its own scale; float32 accumulation."""

    operand = staticmethod(_fwd8)
    product = staticmethod(_bwd8)


exact, fp8 = Exact(), Fp8()


def _mm(quant, eq, a, b):
    return quant.product(jnp.einsum(eq, quant.operand(a), quant.operand(b),
                                    precision=jax.lax.Precision.HIGHEST))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, heads, hd); positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(quant, q, k, v, q_block):
    """Causal GQA attention.  q: (B, S, H, hd); k, v: (B, S, KV, hd)."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(q_block, S)
    nb = S // qb
    blocks = q.reshape(B, nb, qb, H, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = _mm(quant, "bqhd,bkhd->bhqk", qi, k) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        causal = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm(quant, "bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, (jnp.arange(nb), blocks))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def _block(c, quant, x, p):
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    B, S, _ = x.shape
    a = p["attn"]
    h = _rms(x, p["norm1"], eps)
    q = _mm(quant, "bsd,de->bse", h, a["wq"]).reshape(B, S, H, hd)
    k = _mm(quant, "bsd,de->bse", h, a["wk"]).reshape(B, S, KV, hd)
    v = _mm(quant, "bsd,de->bse", h, a["wv"]).reshape(B, S, KV, hd)
    q = _rope(_rms(q, a["q_norm"], eps), c["rope_theta"])
    k = _rope(_rms(k, a["k_norm"], eps), c["rope_theta"])
    o = _attention(quant, q, k, v, Q_BLOCK).reshape(B, S, H * hd)
    x = x + _mm(quant, "bse,ed->bsd", o, a["wo"])
    m = p["mlp"]
    h = _rms(x, p["norm2"], eps)
    g = jax.nn.silu(_mm(quant, "bsd,df->bsf", h, m["w_gate"]))
    u = _mm(quant, "bsd,df->bsf", h, m["w_up"])
    return x + _mm(quant, "bsf,fd->bsd", g * u, m["w_down"])


def loss(c, quant, params, tokens):
    """Mean next-token cross-entropy of a (B, S) int batch."""
    x = params["embed"][tokens].astype(F32)
    body = jax.checkpoint(partial(_block, c, quant))
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, params["layers"])
    h = _rms(x, params["final_norm"], c["rms_norm_eps"])
    B, S, D = h.shape
    rows = h[:, :-1].reshape(-1, D)
    gold = tokens[:, 1:].reshape(-1)
    n = rows.shape[0]
    rb = LOSS_ROWS
    pad = -n % rb
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, rb, D)
    gold = jnp.pad(gold, (0, pad)).reshape(-1, rb)
    keep = (jnp.arange(n + pad) < n).reshape(-1, rb)

    @jax.checkpoint
    def nll(args):
        r, g, k = args
        logits = _mm(quant, "nd,dv->nv", r, params["lm_head"])
        lz = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, g[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(k, lz - pick, 0.0))

    return jnp.sum(jax.lax.map(nll, (rows, gold, keep))) / n


def lr_at(opt, t):
    """Learning rate of update number t (1, 2, ...)."""
    t = jnp.asarray(t, F32)
    warm = opt["lr"] * t / opt["warmup_steps"]
    prog = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    decay = opt["lr"] * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"])
                         * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(t < opt["warmup_steps"], warm, decay)


def rounded(x, dtype):
    """``x`` rounded to the nearest value of ``dtype``, held in float32.

    ``reduce_precision`` and not a cast to ``dtype`` and back: XLA may drop
    such a pair of casts where it allows excess precision, and on the TPU it
    does, which would keep float32 weights where the configuration stores
    bfloat16 ones."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def make_step(c, opt, stored, quant=exact):
    """The reference's AdamW step, as two jitted halves.

    ``grad(params, tokens) -> (loss, gradient)`` and ``update(params, mu,
    nu, grad, t) -> (params, mu, nu, clipped gradient's leaf norms)``, the
    second donating its state.  Parameters are float32 arrays holding values
    of the dtypes the configuration stores them in (``stored``, a tree of
    dtypes): each update is computed in float32 and rounded to that dtype.
    Two halves, so that the moments need not be on the chip while the
    gradient is computed."""

    def grad(params, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda q: loss(c, quant, q, tokens))(params)

    def update(params, mu, nu, g, t):
        norms = _leaf_norms(g)
        gnorm = jnp.sqrt(sum(jnp.square(n) for n in jax.tree.leaves(norms)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / gnorm)
        b1, b2 = opt["b1"], opt["b2"]
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * (clip * x), mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * jnp.square(clip * x),
                          nu, g)
        lr = lr_at(opt, t)
        tf = jnp.asarray(t, F32)
        c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf

        def upd(p, m, v, dtype):
            new = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                            + opt["weight_decay"] * p)
            return rounded(new, dtype)

        params = jax.tree.map(upd, params, mu, nu, stored)
        return params, mu, nu, jax.tree.map(lambda n: clip * n, norms)

    return jax.jit(grad), jax.jit(update, donate_argnums=(0, 1, 2))


def train_readings(c, opt, init, batches, quant=exact):
    """Run one reference step per batch from the weights ``init()`` makes.

    Returns the losses, the norm of each leaf of the first (clipped)
    gradient, and the norm of each leaf's change over all the steps.  The
    moments wait in host memory while a gradient is computed, and the
    initial weights are made again at the end rather than kept: one chip
    then holds the reference at the cell's size."""
    start = init()
    stored = jax.tree.map(lambda x: x.dtype, start)
    params = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(F32), t),
                     donate_argnums=0)(start)
    del start
    grad, update = make_step(c, opt, stored, quant)
    first, losses, moments = None, [], None
    for t, tokens in enumerate(batches, start=1):
        value, g = grad(params, tokens)
        losses.append(float(value))
        if moments is None:
            mu = jax.tree.map(jnp.zeros_like, params)
            nu = jax.tree.map(jnp.zeros_like, params)
        else:
            mu, nu = jax.device_put(moments)
        params, mu, nu, norms = update(params, mu, nu, g, t)
        del g
        if first is None:
            first = jax.tree.map(float, norms)
        moments = jax.device_get((mu, nu))
        del mu, nu
    change = jax.jit(lambda a, b: _leaf_norms(jax.tree.map(
        lambda x, y: x - y.astype(F32), a, b)))(params, init())
    return losses, first, jax.tree.map(float, change)
