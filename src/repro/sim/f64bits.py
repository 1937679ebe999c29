"""IEEE-754 binary64 arithmetic on int64 bit patterns, for the batch engine.

The scalar engines compute latencies, ready times and the MRF token bucket
in Python floats, and the batch engine must reproduce every one of those
values bit for bit.  XLA's f64 is native on a CPU but emulated on a TPU,
and the emulation does not round as IEEE-754 does: on a v5e it changed
the counters of BL and RFC runs.  Integer arithmetic is exact on every
backend, so the batch engine carries each float as its bit pattern in an
int64 and does its few float operations here: conversion from an integer,
addition, subtraction and floor, each rounded to nearest-even exactly as
IEEE-754 specifies.  Products and quotients of configuration constants
are computed on the host (numpy) and enter as bit patterns.

Domain: finite values >= 0, which covers every quantity the engine keeps
(cycle times, latencies, tokens).  On that domain bit patterns order like
the values they encode, so comparisons, ``min`` and ``max`` are integer
ones, and the pattern of ``+inf`` (`INF`) is a sentinel above every value.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

_FRAC = 52                      # fraction bits
_HIDDEN = 1 << _FRAC            # the implicit leading significand bit
_BIAS = 1023
_GRS = 3                        # guard, round and sticky bits while rounding
INF = 0x7FF << _FRAC


def bits(x) -> np.ndarray:
    """Host side: the int64 bit patterns of float64 values."""
    return np.asarray(x, np.float64).view(np.int64)


def from_int(n):
    """Bits of ``float(n)``, exact for 0 <= n < 2**53."""
    n = n.astype(jnp.int64)
    msb = 63 - lax.clz(jnp.maximum(n, 1))
    frac = (n << (_FRAC - msb)) & (_HIDDEN - 1)
    return jnp.where(n == 0, 0, ((msb + _BIAS) << _FRAC) | frac)


def floor(b):
    """``int(x)`` (truncation, i.e. floor for x >= 0) for x < 2**63."""
    e = b >> _FRAC
    sig = (b & (_HIDDEN - 1)) | _HIDDEN
    sh = _BIAS + _FRAC - e
    return jnp.where(e < _BIAS, 0,
                     jnp.where(sh >= 0, sig >> jnp.clip(sh, 0, 63),
                               sig << jnp.clip(-sh, 0, 63)))


def _unpack(b):
    """(exponent, significand): x = sig * 2**(exp - 1075), subnormals too."""
    e = b >> _FRAC
    frac = b & (_HIDDEN - 1)
    return jnp.maximum(e, 1), jnp.where(e > 0, frac | _HIDDEN, frac)


def _align(sig, d):
    """``sig >> d`` with every bit shifted out ORed into the sticky bit."""
    d = jnp.minimum(d, 62)
    lost = (sig & ((jnp.int64(1) << d) - 1)) != 0
    return (sig >> d) | lost.astype(jnp.int64)


def _round_pack(e, sig):
    """Round a significand that carries `_GRS` extra low bits to nearest
    even and pack it with exponent ``e`` (top bit at 52 + `_GRS`, or below
    it only at the smallest exponent, where the result is subnormal)."""
    q = sig >> _GRS
    r = sig & ((1 << _GRS) - 1)
    half = 1 << (_GRS - 1)
    q = q + ((r > half) | ((r == half) & ((q & 1) == 1))).astype(jnp.int64)
    carry = q >> (_FRAC + 1)        # rounding overflowed to 2**53
    return ((e + carry - 1) << _FRAC) + (q >> carry)


def add(a, b):
    """Bits of ``a + b`` for a, b >= 0."""
    a, b = jnp.maximum(a, b), jnp.minimum(a, b)
    ea, sa = _unpack(a)
    eb, sb = _unpack(b)
    s = (sa << _GRS) + _align(sb << _GRS, ea - eb)
    carry = s >> (_FRAC + _GRS + 1)
    return _round_pack(ea + carry, (s >> carry) | (s & carry))


def sub(a, b):
    """Bits of ``a - b`` for a >= b >= 0."""
    ea, sa = _unpack(a)
    eb, sb = _unpack(b)
    s = (sa << _GRS) - _align(sb << _GRS, ea - eb)
    shift = jnp.clip(lax.clz(s) - (63 - _FRAC - _GRS), 0, ea - 1)
    return jnp.where(s == 0, 0, _round_pack(ea - shift, s << shift))
