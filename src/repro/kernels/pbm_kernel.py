"""Pallas TPU kernel for the PBM baseline: z ~ Binomial(m, 1/2 + theta x/c).

Same tiling and in-kernel counter-based RNG as the RQM kernel, so the two
mechanisms are benchmarked on equal footing (one read, one write, m draws).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pbm import PBMParams
from repro.kernels.prng import random_uniform

LANE = 128
DEFAULT_BLOCK_ROWS = 256


def pbm_encode_counters(x, seed, counter, params: PBMParams,
                        compute_dtype=jnp.float32):
    """Element-wise PBM encode given explicit RNG counters (see
    rqm_kernel.rqm_encode_counters for the counter/compute_dtype
    contract — the clip/scale stage runs in ``compute_dtype``, the m
    Bernoulli trials and the emitted counts stay integer-exact)."""
    x = jnp.clip(x.astype(compute_dtype),
                 -jnp.asarray(params.c, compute_dtype),
                 jnp.asarray(params.c, compute_dtype)).astype(jnp.float32)
    p = 0.5 + jnp.float32(params.theta) * x / jnp.float32(params.c)
    z = jnp.zeros(x.shape, jnp.int32)
    for trial in range(params.m):  # static unroll, m Bernoulli(p) draws
        u = random_uniform(seed, counter, stream=trial)
        z = z + (u < p).astype(jnp.int32)
    return z


def _pbm_block(x, seed, base_offset, params: PBMParams):
    rows, cols = x.shape
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    counter = base_offset.astype(jnp.uint32) + row_ids * jnp.uint32(cols) + col_ids
    return pbm_encode_counters(x, seed, counter, params)


def _kernel(seed_ref, off_ref, x_ref, z_ref, *, params: PBMParams,
            block_rows: int):
    pid = pl.program_id(0)
    seed = seed_ref[0, 0]
    base = off_ref[0, 0] + (pid * jnp.uint32(block_rows * LANE)).astype(jnp.uint32)
    z_ref[...] = _pbm_block(x_ref[...], seed, base, params)


def pbm_quantize_2d(
    x: jnp.ndarray,
    seed: jnp.ndarray,
    params: PBMParams,
    *,
    offset,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
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
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # element offset
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        interpret=interpret,
        name="pbm_encode",
    )(seed.reshape(1, 1), offset.reshape(1, 1), x)
