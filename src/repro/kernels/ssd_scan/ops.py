"""Jit wrapper: full SSD scan = Pallas chunk kernel + tiny inter-chunk scan,
and `ssd`, the same forward made differentiable for training.

`ssd`'s backward is the dual form's gradient in XLA, taken one chunk at a
time: a reverse scan over chunks carries the gradient of the state entering
each chunk, and each step differentiates that one chunk's outputs (its
masked (Q, Q) decay products and its outgoing state) from the state that
the forward saved.  So no (B, n_chunks, Q, Q, H) tensor is ever live: a
step holds one chunk's (B, Q, Q, H).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import ssd_chunk_kernel
from .ref import ssd_ref


def _decay(t):
    return jnp.exp(jnp.clip(t, -60.0, 0.0))


def _forward(x, dt, A, Bm, Cm, Q: int, interpret: bool):
    """(y, final state, state entering each chunk (B, nc, H, P, N) f32) for
    S a multiple of Q."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = S // Q

    dA = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
    cum = jnp.cumsum(dA.reshape(Bsz, nc, Q, H), axis=2)          # (B,nc,Q,H)
    y_intra, states = ssd_chunk_kernel(
        x, dt, cum.reshape(Bsz, S, H), Bm, Cm, chunk=Q, interpret=interpret)
    cum = cum.transpose(0, 1, 3, 2)                              # (B,nc,H,Q)
    in_decay = _decay(cum)
    chunk_decay = in_decay[..., -1:]                             # (B,nc,H,1)

    # inter-chunk recurrence over (B,H,P,N) chunk states
    def step(h_prev, inp):
        st, dec = inp                       # (B,H,P,N), (B,H,1)
        h = h_prev * dec[..., None] + st
        return h, h_prev

    init = jnp.zeros((Bsz, H, P, N), jnp.float32)
    final, prev = jax.lax.scan(
        step, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2, 3)))
    prev = prev.transpose(1, 0, 2, 3, 4)     # (B,nc,H,P,N)

    # Y_inter[i] = (C_i . h_prev_chunk) * exp(cum_i)
    Cc = Cm.reshape(Bsz, nc, Q, N).astype(jnp.float32)
    y_inter = jnp.einsum("bcin,bchpn,bchi->bchip", Cc, prev, in_decay)
    y = (y_intra + y_inter).transpose(0, 1, 3, 2, 4).reshape(Bsz, S, H, P)
    return y.astype(x.dtype), final, prev


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 64, interpret: bool = False):
    """Chunked SSD forward.  Same contract as `ssd_ref`.

    x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N)
    -> (y: (B,S,H,P), final_state: (B,H,P,N) f32)
    """
    S = x.shape[1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, final, _ = _forward(x, dt, A, Bm, Cm, Q, interpret)
    if pad:
        y = y[:, :S]
    return y, final


def chunk_outputs(h0, x, dt, A, Bm, Cm):
    """One chunk of the SSD in its dual form, batch-leading: x (B,Q,H,P),
    dt (B,Q,H), A (H,), Bm/Cm (B,Q,N), entering state h0 (B,H,P,N).

    Returns (y (B,Q,H,P), outgoing state (B,H,P,N)): the function the
    kernel and `_forward` compute for that chunk, term by term."""
    Q = x.shape[1]
    cum = jnp.cumsum(dt * A[None, None, :], axis=1)              # (B,Q,H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]                # (B,Q,Q,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    L = jnp.where(causal, _decay(seg), 0.0)
    xdt = x * dt[..., None]
    G = jnp.einsum("bin,bjn->bij", Cm, Bm)
    y = jnp.einsum("bijh,bjhp->bihp", G[..., None] * L, xdt)
    y = y + jnp.einsum("bin,bhpn->bihp", Cm, h0) * _decay(cum)[..., None]
    end = cum[:, -1]                                              # (B,H)
    state = jnp.einsum("bjn,bjhp->bhpn", Bm,
                       xdt * _decay(end[:, None] - cum)[..., None])
    return y, h0 * _decay(end)[..., None, None] + state


def _chunks(t, nc):
    """(B, S, ...) -> (nc, B, Q, ...)."""
    return t.reshape(t.shape[0], nc, -1, *t.shape[2:]).swapaxes(0, 1)


def _unchunk(t):
    """(nc, B, Q, ...) -> (B, S, ...)."""
    t = t.swapaxes(0, 1)
    return t.reshape(t.shape[0], -1, *t.shape[3:])


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, Bm, Cm, chunk, interpret):
    return _forward(x, dt, A, Bm, Cm, chunk, interpret)[0]


def _ssd_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    y, _, prev = _forward(x, dt, A, Bm, Cm, chunk, interpret)
    return y, (x, dt, A, Bm, Cm, prev)


def _ssd_bwd(chunk, interpret, res, dy):
    x, dt, A, Bm, Cm, prev = res
    nc = x.shape[1] // chunk

    def step(dh, inp):
        h0, xc, dtc, bc, cc, dyc = inp
        _, vjp = jax.vjp(chunk_outputs, h0, xc, dtc, A, bc, cc)
        dh0, dx, ddt, dA, dB, dC = vjp((dyc, dh))
        return dh0, (dx, ddt, dA, dB, dC)

    _, (dx, ddt, dA, dB, dC) = jax.lax.scan(
        step, jnp.zeros_like(prev[:, 0]),
        (prev.swapaxes(0, 1), *(_chunks(t, nc) for t in (x, dt, Bm, Cm, dy))),
        reverse=True)
    return (_unchunk(dx), _unchunk(ddt), dA.sum(0), _unchunk(dB),
            _unchunk(dC))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, Bm, Cm, chunk: int, interpret: bool = False):
    """The SSD's outputs y: (B,S,H,P) in x's dtype, forward through the
    chunk kernel and differentiable (a chunk-by-chunk XLA backward).

    Arguments as `ssd_scan`, in float32; S a multiple of ``chunk``."""
    if x.shape[1] % chunk:
        raise ValueError(f"sequence {x.shape[1]} is not a multiple of the "
                         f"chunk {chunk}")
    return _ssd(x, dt, A, Bm, Cm, chunk, interpret)


__all__ = ["ssd", "ssd_scan", "ssd_ref"]
