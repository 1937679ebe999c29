"""Jit wrapper: full SSD scan = Pallas chunk kernel + tiny inter-chunk scan."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import ssd_chunk_kernel
from .ref import ssd_ref


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 64, interpret: bool = False):
    """Chunked SSD forward.  Same contract as `ssd_ref`.

    x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N)
    -> (y: (B,S,H,P), final_state: (B,H,P,N) f32)
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    dA = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
    cum = jnp.cumsum(dA.reshape(Bsz, nc, Q, H), axis=2)          # (B,nc,Q,H)
    y_intra, states = ssd_chunk_kernel(
        x, dt, cum.reshape(Bsz, Sp, H), Bm, Cm, chunk=Q, interpret=interpret)
    cum = cum.transpose(0, 1, 3, 2)                              # (B,nc,H,Q)
    in_decay = jnp.exp(jnp.clip(cum, -60.0, 0.0))
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
    y = (y_intra + y_inter).transpose(0, 1, 3, 2, 4).reshape(Bsz, Sp, H, P)
    if pad:
        y = y[:, :S]
    return y.astype(x.dtype), final


__all__ = ["ssd_scan", "ssd_ref"]
