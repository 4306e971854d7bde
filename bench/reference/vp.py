"""Variable Point numbers as the paper defines them, in plain jax.numpy.

A real x enters on the fixed-point grid FXP(W, F): r = x 2^F rounded to
the nearest integer (ties to even) and saturated to W signed bits.  VP(M,
f) keeps an M-bit signed significand m and the index k of a fraction
length f_k, from the list f in descending order: m = floor(r 2^(f_k - F)),
for the first k at which m fits in M signed bits (the largest f_k, the
most precision), saturated at the last.  Its value is m 2^-f_k.

`grid` returns those values in float32, where every one of them is
exact.  It is written from the definition alone and shares no code with
the program, so a reference that rounds its operands with it reproduces
what a faithful VP datapath holds.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp


def grid(x, W: int, F: int, M: int, f: Sequence[int]):
    """x rounded to FXP(W, F), then to VP(M, f): float32 values."""
    r = jnp.round(jnp.asarray(x, jnp.float32) * 2.0 ** F)
    r = jnp.clip(r, -2.0 ** (W - 1), 2.0 ** (W - 1) - 1)
    lo, hi = -2.0 ** (M - 1), 2.0 ** (M - 1) - 1
    out = jnp.clip(jnp.floor(r * 2.0 ** (f[-1] - F)), lo, hi) \
        * 2.0 ** -f[-1]
    for fk in reversed(f[:-1]):
        m = jnp.floor(r * 2.0 ** (fk - F))
        out = jnp.where((m >= lo) & (m <= hi), m * 2.0 ** -fk, out)
    return out


def default_fractions(W: int, F: int, M: int, E: int) -> tuple:
    """The paper's default fraction lengths (Sec. II-D) for 2^E options:
    the largest is F, the smallest M - (W - F), the others spread evenly
    between them, rounded."""
    K = 2 ** E
    top, bot = F, M - (W - F)
    if K == 1:
        return (top,)
    step = (top - bot) / (K - 1)
    f = sorted({int(round(top - k * step)) for k in range(K)}, reverse=True)
    if len(f) != K:
        raise ValueError(f"VP({M}) over FXP({W},{F}) has no {K} distinct "
                         "fraction lengths")
    return tuple(f)


def pow2_ceil(amax):
    """The smallest power of two at or above amax (1 where amax is 0),
    read off the float's own exponent, so that a power of two maps to
    itself."""
    mant, exp = jnp.frexp(jnp.asarray(amax, jnp.float32))
    s = jnp.ldexp(jnp.ones_like(mant), jnp.where(mant == 0.5, exp - 1, exp))
    return jnp.where(amax > 0, s, 1.0)
