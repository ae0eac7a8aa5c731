"""Counter-based splitmix32 PRNG used inside the Pallas kernels.

Why not ``pltpu.prng_random_bits``: (a) it is unavailable in CPU interpret
mode, which is our kernel-validation runtime; (b) a counter-based generator
is stateless and therefore reproducible across arbitrary shardings and block
shapes — the draw for element ``i`` depends only on (seed, i, stream), never
on block geometry. That makes the kernel bit-exact against the pure-jnp
oracle in ``ref.py`` AND invariant under re-tiling, which we assert in tests.

All ops are uint32 add/mul/xor/shift — VPU-friendly on TPU, exact on CPU.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# numpy-uint32 scalar constants: inlined as jaxpr literals, so Pallas kernel
# bodies using them capture no traced constants (jnp constants would).
GOLDEN = np.uint32(0x9E3779B9)  # splitmix increment
STREAM_SALT = np.uint32(0xBF58476D)

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)


def mix32(z: jnp.ndarray) -> jnp.ndarray:
    """splitmix32 finalizer (murmur3-style avalanche)."""
    z = z.astype(jnp.uint32)
    z = (z ^ (z >> 16)) * _M1
    z = (z ^ (z >> 13)) * _M2
    z = z ^ (z >> 16)
    return z


def random_bits(seed: jnp.ndarray, counter: jnp.ndarray, stream: int) -> jnp.ndarray:
    """uint32 random bits for (seed, per-element counter, static stream id)."""
    s = seed.astype(jnp.uint32) + np.uint32((int(stream) * int(STREAM_SALT)) & 0xFFFFFFFF)
    return mix32(s + counter.astype(jnp.uint32) * GOLDEN)


def uniform01(bits: jnp.ndarray) -> jnp.ndarray:
    """Map uint32 bits to float32 uniforms in [0, 1) using the top 24 bits.

    The cast goes through int32: Mosaic has no uint32 -> float32 convert,
    and ``bits >> 8 < 2**24`` is exact as a signed value and as a float.
    """
    top24 = (bits >> 8).astype(jnp.int32)
    return top24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def random_uniform(seed, counter, stream: int) -> jnp.ndarray:
    return uniform01(random_bits(seed, counter, stream))
