"""Pallas TPU kernel for the QMGeo-style truncated-geometric quantizer.

Same tiling and in-kernel counter-based RNG as the RQM/PBM kernels (see
rqm_kernel.py for the design rationale). Two uniform streams per element:
stream 0 drives the stochastic rounding, stream 1 the inverse-CDF draw of
the truncated geometric noise.

Unlike the RQM kernel (which re-implements Algorithm 2's level search in
tiled form), the QMGeo core ``core.qmgeo.quantize_with_uniforms`` is
already purely element-wise with a static m-level unroll and no per-level
axis in memory — so the kernel body calls it DIRECTLY. Kernel == mechanism
reference by construction, not merely by test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.qmgeo import QMGeoParams, quantize_with_uniforms
from repro.kernels.prng import random_uniform

LANE = 128
DEFAULT_BLOCK_ROWS = 256


def qmgeo_encode_counters(x, seed, counter, params: QMGeoParams,
                          compute_dtype=jnp.float32):
    """Element-wise QMGeo encode given explicit RNG counters (see
    rqm_kernel.rqm_encode_counters for the counter/compute_dtype
    contract). Stream 0 drives the stochastic rounding, stream 1 the
    truncated-geometric noise; the clip happens inside
    ``quantize_with_uniforms``, so the compute_dtype round-trip here only
    narrows the raw input's mantissa before that clip."""
    x = x.astype(compute_dtype).astype(jnp.float32)
    u_round = random_uniform(seed, counter, stream=0)
    u_noise = random_uniform(seed, counter, stream=1)
    return quantize_with_uniforms(x, u_round, u_noise, params)


def _qmgeo_block(x, seed, base_offset, params: QMGeoParams):
    """Shared element-wise body (kernel, fused-jnp CPU path, and ref.py)."""
    rows, cols = x.shape
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    counter = base_offset.astype(jnp.uint32) + row_ids * jnp.uint32(cols) + col_ids
    return qmgeo_encode_counters(x, seed, counter, params)


def _kernel(seed_ref, off_ref, x_ref, z_ref, *, params: QMGeoParams,
            block_rows: int):
    pid = pl.program_id(0)
    seed = seed_ref[0, 0]
    base = off_ref[0, 0] + (pid * jnp.uint32(block_rows * LANE)).astype(jnp.uint32)
    z_ref[...] = _qmgeo_block(x_ref[...], seed, base, params)


def qmgeo_quantize_2d(
    x: jnp.ndarray,
    seed: jnp.ndarray,
    params: QMGeoParams,
    *,
    offset,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """pallas_call entry point on a pre-tiled (rows, 128) float array.

    rows must be a multiple of block_rows; use ops.qmgeo for arbitrary
    shapes. seed: uint32 scalar array of shape (1, 1). offset:
    uint32 scalar (may be traced) — element (r, c) draws the counter of
    flat element ``offset + r*128 + c``.
    """
    rows, cols = x.shape
    if cols != LANE:
        raise ValueError(f"expected lane dim {LANE}, got {cols}")
    if rows % block_rows != 0:
        raise ValueError(f"rows {rows} not a multiple of block_rows {block_rows}")
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, params=params, block_rows=block_rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # seed: broadcast scalar
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # element offset
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        interpret=interpret,
    )(seed.reshape(1, 1), offset.reshape(1, 1), x)
