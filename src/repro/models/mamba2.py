"""Mamba2 (SSD — state-space duality) blocks, chunked scan + decode step.

Implements the SSD dual form from arXiv:2405.21060: within chunks of length Q
the output is computed with dense matmuls (MXU-friendly), while chunk-final
states are carried by an associative `lax.scan` — this is the structure the
`kernels/ssd_scan` Pallas kernel accelerates.

`ssd` is the one entry point a block calls.  On one TPU it runs the
kernel's forward under a custom VJP (`kernels.ssd_scan.ops.ssd`); elsewhere,
and where the active sharding rules' mesh holds more than one device,
`ssd_chunked` in XLA.  ``SSD_STATS`` counts the path each call took on the
platform the step was lowered for (`repro.models.paths`).  The mixer runs
under the ``jax.named_scope`` ``ssm``.

Shapes follow the minimal Mamba2 formulation with n_groups=1:
  x:  (B, S, H, P)    per-head inputs (P = head dim)
  dt: (B, S, H)       softplus-positive step sizes
  B,C:(B, S, N)       input/output projections (shared across heads)
  A:  (H,)            negative decay rates
State: (B, H, P, N).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import CONV_K  # depthwise conv kernel width
from repro.distributed.sharding import active_rules
from repro.kernels.ssd_scan.ops import ssd as ssd_kernel

from . import paths
from .layers import _init, rms_norm

# Which path each SSD call took: ``kernel_calls`` and ``xla_calls``.
SSD_STATS = paths.counter("ssd")


def reset_ssd_stats() -> dict:
    return paths.reset(SSD_STATS)


def init_mamba2(key, d_model, d_state, headdim, expand, dtype,
                conv_bias: bool = False):
    d_inner = expand * d_model
    nheads = d_inner // headdim
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(d_model)
    d_in_proj = 2 * d_inner + 2 * d_state + nheads  # z, x, B, C, dt
    params = {
        "in_proj": _init(ks[0], (d_model, d_in_proj), s, dtype),
        "conv": _init(ks[1], (CONV_K, d_inner + 2 * d_state), 0.5, dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)).astype(jnp.float32),
        "dt_bias": jnp.zeros((nheads,), dtype=jnp.float32),
        "D": jnp.ones((nheads,), dtype=jnp.float32),
        "norm": jnp.ones((d_inner,), dtype=jnp.float32),
        "out_proj": _init(ks[2], (d_inner, d_model), 1.0 / math.sqrt(d_inner), dtype),
    }
    axes = {
        "in_proj": ("embed", "ffn"),
        "conv": (None, "ffn"),
        "A_log": (None,),
        "dt_bias": (None,),
        "D": (None,),
        "norm": ("ffn",),
        "out_proj": ("ffn", "embed"),
    }
    if conv_bias:
        params["conv_bias"] = jnp.zeros((d_inner + 2 * d_state,), dtype)
        axes["conv_bias"] = ("ffn",)
    return params, axes


def _split_proj(zxbcdt, d_inner, d_state):
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * d_state]
    dt = zxbcdt[..., 2 * d_inner + 2 * d_state:]
    return z, xBC, dt


def _causal_conv(xBC, conv_w, state=None, bias=None):
    """Depthwise causal conv along seq.  xBC: (B,S,C); conv_w: (K,C);
    ``bias`` (C,) or None.

    With ``state`` (B, K-1, C) performs streaming conv (decode)."""
    B, S, C = xBC.shape
    if state is not None:
        xBC = jnp.concatenate([state, xBC], axis=1)
        new_state = xBC[:, -(CONV_K - 1):]
    else:
        xBC = jnp.pad(xBC, ((0, 0), (CONV_K - 1, 0), (0, 0)))
        new_state = xBC[:, -(CONV_K - 1):]
    out = sum(xBC[:, k:k + S] * conv_w[k][None, None] for k in range(CONV_K))
    if bias is not None:
        out = out + bias
    return jax.nn.silu(out), new_state


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """SSD forward over a full sequence (training / prefill).

    x: (B,S,H,P) dt: (B,S,H) A: (H,) Bm/Cm: (B,S,N).
    Returns (y: (B,S,H,P), final_state: (B,H,P,N)).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = -(-S // Q)
    pad = nc * Q - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    S_p = nc * Q

    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    dA = dtc * A[None, None, None, :]          # (B,nc,Q,H)  (negative)
    cum = jnp.cumsum(dA, axis=2)               # within-chunk cumulative
    # decay from position j to end of chunk / from start to position i
    seg_end = cum[:, :, -1:, :] - cum          # (B,nc,Q,H): end-of-chunk decay
    # intra-chunk causal kernel L[i,j] = exp(cum_i - cum_j) for i >= j
    li = cum[:, :, :, None, :]                 # i index
    lj = cum[:, :, None, :, :]                 # j index
    L = jnp.exp(jnp.clip(li - lj, -60.0, 0.0))
    idx = jnp.arange(Q)
    causal = (idx[:, None] >= idx[None, :])
    L = L * causal[None, None, :, :, None]

    xdt = xc * dtc[..., None]                  # dt-weighted inputs
    # intra-chunk: y[i] = C_i . sum_j L[i,j] B_j x_j dt_j
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # (B,nc,Q,Q)
    M = G[..., None] * L                       # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xdt)

    # chunk-final states: sum_j exp(cum_end - cum_j) B_j x_j dt_j
    decay_to_end = jnp.exp(jnp.clip(seg_end, -60.0, 0.0))  # (B,nc,Q,H)
    chunk_state = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", Bc, decay_to_end, xdt)

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.clip(cum[:, :, -1, :], -60.0, 0.0))  # (B,nc,H)

    def step(h_prev, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        h = h_prev * dec[..., None, None] + st
        return h, h_prev

    init = jnp.zeros((Bsz, H, P, N), dtype=x.dtype)
    final, prev_states = jax.lax.scan(
        step,
        init,
        (chunk_state.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)

    # inter-chunk contribution: y[i] += (C_i . h_prev) * exp(cum_i)
    in_decay = jnp.exp(jnp.clip(cum, -60.0, 0.0))  # (B,nc,Q,H)
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp", Cc, prev_states, in_decay)

    y = (y_intra + y_inter).reshape(Bsz, S_p, H, P)
    if pad:
        y = y[:, :S]
    return y, final


def ssd_decode_step(state, x, dt, A, Bm, Cm):
    """One-token SSD update.  state: (B,H,P,N); x: (B,H,P); dt: (B,H);
    Bm/Cm: (B,N).  Returns (y, new_state)."""
    dA = jnp.exp(jnp.clip(dt * A[None, :], -60.0, 0.0))  # (B,H)
    xdt = x * dt[..., None]
    upd = jnp.einsum("bhp,bn->bhpn", xdt, Bm)
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm)
    return y, new_state


def ssd_on_kernel(seq: int, chunk: int) -> bool:
    """Whether an SSD over ``seq`` positions may take the chunk kernel on a
    TPU: the sequence in whole chunks, and at most one device in the active
    rules' mesh (the kernel is not partitioned across devices)."""
    rules = active_rules()
    return (seq % min(chunk, seq) == 0
            and (rules is None or rules.mesh.size <= 1))


def _xla_ssd(x, dt, A, Bm, Cm, chunk):
    return ssd_chunked(paths.tag(x, "ssd", "xla"), dt, A, Bm, Cm, chunk)[0]


def _kernel_ssd(x, dt, A, Bm, Cm, chunk):
    return ssd_kernel(paths.tag(x, "ssd", "kernel"), dt, A, Bm, Cm,
                      chunk=min(chunk, x.shape[1]))


def ssd(x, dt, A, Bm, Cm, chunk: int):
    """The SSD's outputs y: (B,S,H,P) over a whole sequence, arguments as
    `ssd_chunked`: the chunk kernel on one TPU where `ssd_on_kernel`, else
    `ssd_chunked`."""
    xla = partial(_xla_ssd, chunk=chunk)
    if not ssd_on_kernel(x.shape[1], chunk):
        return xla(x, dt, A, Bm, Cm)
    return jax.lax.platform_dependent(
        x, dt, A, Bm, Cm, tpu=partial(_kernel_ssd, chunk=chunk), default=xla)


@jax.named_scope("ssm")
def mamba2_block(params, x, *, d_state, headdim, expand, chunk,
                 norm_eps=1e-5):
    """Full Mamba2 mixer over a sequence.  x: (B,S,D)."""
    B, S, D = x.shape
    d_inner = expand * D
    nheads = d_inner // headdim
    zxbcdt = x @ params["in_proj"]
    z, xBC, dt = _split_proj(zxbcdt, d_inner, d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    xBC, _ = _causal_conv(xBC, params["conv"], bias=params.get("conv_bias"))
    xs = xBC[..., :d_inner].reshape(B, S, nheads, headdim)
    Bm = xBC[..., d_inner:d_inner + d_state]
    Cm = xBC[..., d_inner + d_state:]
    A = -jnp.exp(params["A_log"])
    y = ssd(xs.astype(jnp.float32), dt, A, Bm.astype(jnp.float32),
            Cm.astype(jnp.float32), chunk)
    y = y + xs.astype(jnp.float32) * params["D"][None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], norm_eps)
    return y @ params["out_proj"]


def mamba2_decode(params, x, cache, *, d_state, headdim, expand, norm_eps=1e-5):
    """One-token decode.  x: (B,1,D); cache: {'conv': (B,K-1,C), 'ssm': (B,H,P,N)}."""
    B, S, D = x.shape
    d_inner = expand * D
    nheads = d_inner // headdim
    zxbcdt = x @ params["in_proj"]
    z, xBC, dt = _split_proj(zxbcdt, d_inner, d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]  # (B,H)
    xBC, new_conv = _causal_conv(xBC, params["conv"], cache["conv"],
                                 params.get("conv_bias"))
    xs = xBC[:, 0, :d_inner].reshape(B, nheads, headdim)
    Bm = xBC[:, 0, d_inner:d_inner + d_state]
    Cm = xBC[:, 0, d_inner + d_state:]
    A = -jnp.exp(params["A_log"])
    y, new_ssm = ssd_decode_step(cache["ssm"].astype(jnp.float32),
                                 xs.astype(jnp.float32), dt, A,
                                 Bm.astype(jnp.float32), Cm.astype(jnp.float32))
    y = y + xs.astype(jnp.float32) * params["D"][None, :, None]
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], norm_eps)
    return y @ params["out_proj"], {"conv": new_conv, "ssm": new_ssm}
