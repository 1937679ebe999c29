"""Plain float32 reference for training a Granite-4.0-H hybrid decoder.

Written from the published architecture (Hugging Face
``GraniteMoeHybridForCausalLM``, the ``config.json`` of
ibm-granite/granite-4.0-h-micro; Mamba-2 and its SSD from arXiv:2405.21060),
not from the program:

* token embedding times ``embedding_multiplier``; then one layer per entry
  of ``layer_types``, each ``h = x + r * mixer(rms(x))`` and
  ``h + r * mlp(rms(h))`` with ``r = residual_multiplier``; a final RMSNorm;
  logits ``h @ embedding^T / logits_scaling`` (the head is tied);
* RMSNorm ``x / sqrt(mean(x^2) + eps) * w``;
* Mamba-2 mixer (one group, no projection bias): ``in_proj`` to the gate
  ``z`` (``d_inner``), ``xBC`` (``d_inner + 2 d_state``) and ``dt`` (one
  per head); a depthwise causal convolution of width ``mamba_d_conv`` with
  bias over ``xBC``, then SiLU, split into ``x``, ``B``, ``C``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the SSD
  ``y_i = sum_{j<=i} exp(sum_{j<t<=i} dt_t A) (C_i . B_j) dt_j x_j
  + D x_i`` per head; the gated norm ``rms(y * silu(z)) * w`` over all of
  ``d_inner``; ``out_proj``;
* attention: q, k, v projections without bias, no position embedding
  (``position_embedding_type`` ``nope``), causal softmax of the scores times
  ``attention_multiplier``, grouped-query heads, output projection;
* MLP: ``down(silu(gate(x)) * up(x))`` (the two halves of Granite's
  ``shared_mlp.input_linear``);
* loss: next-token cross-entropy, the mean over every position but the
  last of each row;
* AdamW: `bench.refs.qwen3`'s, as the job's file states it.

The SSD here is the masked quadratic (dual) form over the whole sequence,
not the program's chunked state passing.  Its decays are segment sums:
within a block of ``BLOCK`` positions by a cumulative sum of that block
alone (forward, backward, or of a masked square for the diagonal block),
and across blocks by a sum of the whole blocks between, so that no float32
cumulative sum spans more than one block (over 8192 steps one loses the
short decays to rounding).

Departures, none of them in the mathematics: the weights are read in the
program's layout (each maximal run of one kind of layer stacked, and
scanned here, the conv kernel as (width, channels), the MLP's halves as
``w_gate`` and ``w_up``).  Every
matrix product runs in float32 at ``highest`` precision; to fit one chip at
the cell's size each layer is recomputed in the backward pass, the SSD and
attention take blocks of queries, the loss takes blocks of rows, and the
AdamW update takes groups of leaves: the same mathematics, in pieces.

``quant`` is `bench.refs.qwen3`'s: `exact` gives the reference, `fp8` the
control, which computes every matrix product in float8.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.qwen3 import LOSS_ROWS, _mm, exact, fp8, make_step  # noqa: F401

F32 = jnp.float32
# positions per SSD and attention query block, and per segment-sum block:
# sizes of the pieces, chosen to fit one chip at the cell's size; they
# change no result
BLOCK = 128
# groups of leaves per AdamW update, so that one group's moments at a time
# sit beside the weights and the gradient on the chip
UPDATE_GROUPS = 4


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def runs_of(c, params):
    """(kind, parameters stacked over its layers) of each maximal run of one
    kind in ``layer_types``, in order: the program's layout of the
    weights."""
    kinds = c["layer_types"]
    starts = [i for i in range(len(kinds)) if i == 0 or kinds[i] != kinds[i - 1]]
    return [(kinds[i], run) for i, run in zip(starts, params["layers"],
                                               strict=True)]


def _block_sums(dA):
    """Segment sums of ``dA`` (B, nb, T, H) within and across blocks:
    ``fwd[t] = sum_{u<=t}``, ``tail[t] = sum_{u>t}`` in a block, each block's
    total, and ``diag[i, j] = sum_{j<t<=i}`` (-inf above the diagonal)."""
    T = dA.shape[2]
    fwd = jnp.cumsum(dA, axis=2)
    shifted = jnp.concatenate([dA[:, :, 1:], jnp.zeros_like(dA[:, :, :1])],
                              axis=2)
    tail = jnp.flip(jnp.cumsum(jnp.flip(shifted, 2), axis=2), 2)
    total = jnp.sum(dA, axis=2)
    below = jnp.tril(jnp.ones((T, T), bool), -1)               # t > j
    # diag[..., i, j] = sum over t <= i of dA_t [t > j]: a cumulative sum of
    # one block down a masked square, no difference of two long sums
    masked = jnp.where(below[None, None, :, :, None],
                       dA[:, :, :, None, :], 0.0)               # (B,nb,t,j,H)
    diag = jnp.cumsum(masked, axis=2)
    upper = jnp.triu(jnp.ones((T, T), bool), 1)
    diag = jnp.where(upper[None, None, :, :, None], -jnp.inf, diag)
    return fwd, tail, total, diag


def ssd(quant, x, dt, A, Bm, Cm, D):
    """The SSD's y: (B, S, H, P), in its masked quadratic form, by query
    blocks.  x (B, S, H, P), dt (B, S, H), A, D (H,), Bm, Cm (B, S, N)."""
    Bsz, S, H, P = x.shape
    T = min(BLOCK, S)
    nb = S // T
    dA = (dt * A).reshape(Bsz, nb, T, H)
    fwd, tail, total, diag = _block_sums(dA)
    blk = jnp.arange(nb)
    # between[q, k] = sum of the whole blocks strictly between k and q
    inside = ((blk[None, :, None] < blk[None, None, :])
              & (blk[None, None, :] < blk[:, None, None]))      # (q, k, m)
    between = jnp.einsum("qkm,bmh->bqkh", inside.astype(F32), total,
                         precision=jax.lax.Precision.HIGHEST)
    xdt = x * dt[..., None]

    @jax.checkpoint
    def one(q):
        cq = jax.lax.dynamic_slice_in_dim(Cm, q * T, T, axis=1)  # (B,T,N)
        fq = jax.lax.dynamic_index_in_dim(fwd, q, 1, keepdims=False)
        bq = jax.lax.dynamic_index_in_dim(between, q, 1, keepdims=False)
        dq = jax.lax.dynamic_index_in_dim(diag, q, 1, keepdims=False)
        off = (fq[:, :, None, None, :] + bq[:, None, :, None, :]
               + tail[:, None])                                  # (B,T,nb,T,H)
        seg = jnp.where((blk < q)[None, None, :, None, None], off, -jnp.inf)
        seg = jnp.where((blk == q)[None, None, :, None, None],
                        dq[:, :, None], seg)
        decay = jnp.exp(seg).reshape(Bsz, T, S, H)
        G = _mm(quant, "bin,bjn->bij", cq, Bm)                   # (B,T,S)
        return _mm(quant, "bijh,bjhp->bihp", G[..., None] * decay, xdt)

    y = jax.lax.map(one, jnp.arange(nb))                         # (nb,B,T,H,P)
    y = y.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, P)
    return y + x * D[None, None, :, None]


def _conv(xbc, w, b):
    """Depthwise causal convolution: out[t] = sum_k w[k] x[t - K + 1 + k]
    + b.  xbc (B, S, C); w (K, C)."""
    K, S = w.shape[0], xbc.shape[1]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, k:k + S] * w[k] for k in range(K)) + b


def mamba(c, quant, p, h):
    eps = c["rms_norm_eps"]
    H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    Bsz, S, _ = h.shape
    d_inner = H * P
    proj = _mm(quant, "bsd,de->bse", h, p["in_proj"])
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:2 * d_inner + 2 * N]
    dt = jax.nn.softplus(proj[..., 2 * d_inner + 2 * N:] + p["dt_bias"])
    xbc = jax.nn.silu(_conv(xbc, p["conv"], p["conv_bias"]))
    x = xbc[..., :d_inner].reshape(Bsz, S, H, P)
    Bm, Cm = xbc[..., d_inner:d_inner + N], xbc[..., d_inner + N:]
    y = ssd(quant, x, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["D"])
    y = y.reshape(Bsz, S, d_inner) * jax.nn.silu(z)
    return _mm(quant, "bse,ed->bsd", _rms(y, p["norm"], eps), p["out_proj"])


def attention(c, quant, p, h):
    """Causal GQA attention without position embedding, by query blocks."""
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    Bsz, S, _ = h.shape
    q = _mm(quant, "bsd,de->bse", h, p["wq"]).reshape(Bsz, S, H, hd)
    k = _mm(quant, "bsd,de->bse", h, p["wk"]).reshape(Bsz, S, KV, hd)
    v = _mm(quant, "bsd,de->bse", h, p["wv"]).reshape(Bsz, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    T = min(BLOCK, S)
    nb = S // T
    blocks = q.reshape(Bsz, nb, T, H, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = _mm(quant, "bqhd,bkhd->bhqk", qi, k) * c["attention_multiplier"]
        qpos = i * T + jnp.arange(T)
        causal = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return _mm(quant, "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, (jnp.arange(nb), blocks))
    o = o.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H * hd)
    return _mm(quant, "bse,ed->bsd", o, p["wo"])


def _layer(c, quant, kind, x, p):
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    mixer = mamba if kind == "mamba" else attention
    x = x + r * mixer(c, quant, p["mixer"], _rms(x, p["norm1"], eps))
    m = p["mlp"]
    h = _rms(x, p["norm2"], eps)
    g = jax.nn.silu(_mm(quant, "bsd,df->bsf", h, m["w_gate"]))
    u = _mm(quant, "bsd,df->bsf", h, m["w_up"])
    return x + r * _mm(quant, "bsf,fd->bsd", g * u, m["w_down"])


def loss(c, quant, params, tokens):
    """Mean next-token cross-entropy of a (B, S) int batch."""
    table = params["embed"]
    x = table[tokens].astype(F32) * c["embedding_multiplier"]
    for kind, run in runs_of(c, params):
        body = jax.checkpoint(partial(_layer, c, quant, kind))
        x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, run)
    h = _rms(x, params["final_norm"], c["rms_norm_eps"])
    D = h.shape[-1]
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
        rw, g, k = args
        logits = _mm(quant, "nd,vd->nv", rw, table) / c["logits_scaling"]
        lz = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, g[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(k, lz - pick, 0.0))

    return jnp.sum(jax.lax.map(nll, (rows, gold, keep))) / n


def _groups(sizes, n):
    """``range(len(sizes))`` cut into at most ``n`` runs of leaves of about
    equal total size."""
    cuts = np.searchsorted(np.cumsum(sizes), np.sum(sizes) * np.arange(1, n) / n)
    return [g.tolist() for g in np.split(np.arange(len(sizes)), cuts) if len(g)]


def train_readings(c, opt, init, batches, quant=exact):
    """Run one reference step per batch from the weights ``init()`` makes.

    Returns the losses, the norm of each leaf of the first (clipped)
    gradient, and the norm of each leaf's change over all the steps.  The
    gradient is clipped to its global norm as AdamW's file states it, and
    `bench.refs.qwen3`'s update then runs on each group of leaves in turn
    (within a group the clipped gradient's norm is at most the limit, so
    the update clips no further); the moments wait in host memory."""
    start = init()
    leaves, treedef = jax.tree.flatten(start)
    stored = [x.dtype for x in leaves]
    params = jax.jit(lambda t: [x.astype(F32) for x in t],
                     donate_argnums=0)(leaves)
    del start, leaves
    def grad_of(p, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda q: loss(
                c, quant, jax.tree.unflatten(treedef, q), tokens))(p)

    grad = jax.jit(grad_of)
    clipped = jax.jit(lambda g: [x * jnp.minimum(1.0, opt["grad_clip"] / jnp.sqrt(
        sum(jnp.sum(jnp.square(y)) for y in g))) for x in g])
    groups = _groups([math.prod(x.shape) for x in params], UPDATE_GROUPS)
    updates = [make_step(c, opt, [stored[i] for i in grp], quant)[1]
               for grp in groups]
    first, losses, moments = None, [], None
    for t, tokens in enumerate(batches, start=1):
        value, g = grad(params, tokens)
        losses.append(float(value))
        g = clipped(g)
        norms = [None] * len(params)
        new_moments = []
        for gi, (grp, update) in enumerate(zip(groups, updates)):
            p = [params[i] for i in grp]
            if moments is None:
                mu = [jnp.zeros_like(x) for x in p]
                nu = [jnp.zeros_like(x) for x in p]
            else:
                mu, nu = jax.device_put(moments[gi])
            p, mu, nu, n = update(p, mu, nu, [g[i] for i in grp], t)
            for i, x, ni in zip(grp, p, n):
                params[i], norms[i] = x, ni
            new_moments.append(jax.device_get((mu, nu)))
            del mu, nu
        moments = new_moments
        del g
        if first is None:
            first = jax.tree.unflatten(treedef, [float(x) for x in norms])
    change = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x - y.astype(F32)))) for x, y in zip(a, b)])(
            params, jax.tree.leaves(init()))
    return (losses, first,
            jax.tree.unflatten(treedef, [float(x) for x in change]))
