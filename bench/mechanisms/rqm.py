"""Plain reference of the Randomized Quantization Mechanism, Algorithm 2 of
arXiv:2306.11913, in straightforward ``jax.numpy``. It imports nothing of
the program.

A client's vector is clipped to ``[-c, c]`` and coordinate ``j`` is
encoded on the grid ``B(l) = -(c + delta) + l * step``,
``step = 2 (c + delta) / (m - 1)``: each interior level is kept with
probability ``q`` (endpoints always), and the value rounds at random to
the nearest kept level below or above it, up with probability
``(x - B(lo)) / (B(hi) - B(lo))``. The server decodes the sum of ``n``
clients' levels as ``-(c + delta) + sum * 2 (c + delta) / (n (m - 1))``.

The uniforms are the mechanism's stated stream: a splitmix32 hash of the
round seed (``bits(k_enc)``), the element's position ``i * dim + j`` in
the cohort, and a stream id (interior level ``l`` draws stream ``l``, the
rounding draws stream ``m``); the top 24 bits make a uniform in [0, 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)
_SALT = 0xBF58476D
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)


def uniforms(seed, counter, stream: int):
    """float32 uniforms in [0, 1) for uint32 ``seed`` and ``counter``."""
    z = (seed + np.uint32((stream * _SALT) & 0xFFFFFFFF)) + counter * _GOLDEN
    z = (z ^ (z >> 16)) * _M1
    z = (z ^ (z >> 13)) * _M2
    z = z ^ (z >> 16)
    return (z >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)


def round_seed(k_enc):
    """The round's stream seed from its encode key."""
    return jax.random.bits(k_enc, (), jnp.uint32)


def clip(*, c, delta, m, q) -> float:
    """Every coordinate is clipped to ``[-c, c]``."""
    return c


@functools.partial(jax.jit, static_argnames=("c", "delta", "m", "q"))
def encode(x, seed, first, *, c, delta, m, q):
    """RQM levels of one client's vector ``x``, clipped here; ``first`` is
    the position of its element 0 in the cohort's element order."""
    q = np.float32(q)
    x_max = np.float32(c + delta)
    step = np.float32(2.0 * (c + delta) / (m - 1))
    x = jnp.clip(x, -np.float32(c), np.float32(c))
    counter = first + jnp.arange(x.shape[0], dtype=jnp.uint32)
    j = jnp.clip(jnp.floor((x + x_max) / step), 0, m - 2).astype(jnp.int32)
    lo = jnp.zeros_like(j)
    hi = jnp.full_like(j, m - 1)
    for level in range(1, m - 1):
        kept = uniforms(seed, counter, level) < q
        lo = jnp.where(kept & (level <= j), level, lo)
        hi = jnp.where(kept & (level > j) & (level < hi), level, hi)
    b_lo = -x_max + lo.astype(jnp.float32) * step
    b_hi = -x_max + hi.astype(jnp.float32) * step
    up = uniforms(seed, counter, m) < (x - b_lo) / (b_hi - b_lo)
    return jnp.where(up, hi, lo)


def decode(z_sum, n: int, *, c, delta, m, q):
    """The decoded mean gradient from the sum of ``n`` clients' levels."""
    x_max = c + delta
    scale = 2.0 * x_max / (n * (m - 1))
    return -x_max + z_sum.astype(jnp.float32) * scale


def level(n: int, *, c, delta, m, q) -> float:
    """How far one level of the released sum moves the decoded gradient."""
    return 2.0 * (c + delta) / (n * (m - 1))
