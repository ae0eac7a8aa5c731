"""Pallas TPU kernel: fused clip -> level-subsample -> randomized-round (RQM).

This is the per-coordinate hot loop of the paper's mechanism (Algorithm 2),
executed on every gradient element every step — the compute hot-spot the
paper's technique introduces on top of plain DP-SGD.

TPU adaptation (vs the paper's TF/GPU reference):
  * The input is tiled into (block_rows, 128) VMEM blocks — the lane dim is
    the native 128 and block_rows a multiple of 8, so all element-wise math
    maps onto full VPU vregs.
  * The "nearest kept level below/above" search is a STATIC unrolled loop
    over the m-2 interior levels with running max/min accumulators — no
    gather, no data-dependent control flow, no (block, m) intermediate in
    VMEM. m is small (16 in the paper) so the unroll is cheap.
  * Randomness is an in-kernel counter-based splitmix32 (see prng.py): one
    draw per (element, interior level) + one rounding draw, derived from a
    scalar seed + the element's global offset. No RNG state, no extra HBM
    traffic (a uniforms-as-input design would read m+1 extra floats per
    element — 17x the input bytes; in-kernel hashing reads 0).

The kernel is a single pass: x is read once, z written once -> arithmetic
intensity ~ (m * ~10 VPU ops) / 8 bytes, i.e. compute-dense enough to hide
behind the gradient all-reduce it replaces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.grid import RQMParams
from repro.kernels.prng import random_uniform

LANE = 128
SUBLANE = 8  # f32 sublane height: block_rows must stay a multiple of this
DEFAULT_BLOCK_ROWS = 256  # (256, 128) f32 = 128 KiB per buffer in VMEM


def pick_block_rows(n_elements: int, requested: int = DEFAULT_BLOCK_ROWS) -> int:
    """Clamp the block height for a flat input of ``n_elements``.

    The wrappers in ops.py pad a flat vector to whole (block_rows, LANE)
    tiles; with the fixed default a tiny leaf (a bias vector in the
    distributed step) would pad to a full 32K-element tile — 500x wasted
    work. Clamping to the input's own (sublane-aligned) row count keeps
    padding below one sublane row without changing any output: the
    counter-based RNG makes the kernel invariant to tiling.
    """
    rows_needed = -(-n_elements // LANE)
    rows_needed = -(-rows_needed // SUBLANE) * SUBLANE
    return max(SUBLANE, min(requested, rows_needed))


def rqm_encode_counters(x, seed, counter, params: RQMParams,
                        compute_dtype=jnp.float32):
    """The element-wise RQM encode given EXPLICIT per-element RNG counters.

    This is the single source of the mechanism's per-element math: the
    contiguous-block body below, the oracle in ref.py, and the fused
    round-sum kernel (kernels/fused_round_kernel.py — whose (block_rows,
    128) column tiles are NOT contiguous in the conceptual flat input, so
    they must supply their own counters) all delegate here. RNG draws
    depend only on (seed, counter), never on tiling.

    ``compute_dtype`` is the clip/scale-stage precision: float32 (default,
    bit-exact contract) or bfloat16 (halves the VPU input width on TPU;
    the level search and the emitted levels stay integer-exact either
    way — only the clipped input loses mantissa bits).
    """
    m = params.m
    q = jnp.float32(params.q)
    x_max = jnp.float32(params.x_max)
    step = jnp.float32(params.step)

    x = jnp.clip(x.astype(compute_dtype),
                 -jnp.asarray(params.c, compute_dtype),
                 jnp.asarray(params.c, compute_dtype)).astype(jnp.float32)

    # Bin index j: x in [B(j), B(j+1)), clipped for boundary round-off.
    j = jnp.clip(jnp.floor((x + x_max) / step), 0, m - 2).astype(jnp.int32)

    # Running nearest-kept-level accumulators. Endpoints are always kept.
    i_lo = jnp.zeros_like(j)
    i_hi = jnp.full_like(j, m - 1)
    for lvl in range(1, m - 1):  # static unroll over interior levels
        u = random_uniform(seed, counter, stream=lvl)
        keep = u < q
        below = jnp.int32(lvl) <= j
        i_lo = jnp.where(keep & below, jnp.int32(lvl), i_lo)  # ascending -> max
        i_hi = jnp.minimum(i_hi, jnp.where(keep & ~below, jnp.int32(lvl), m - 1))

    b_lo = -x_max + i_lo.astype(jnp.float32) * step
    b_hi = -x_max + i_hi.astype(jnp.float32) * step
    p_up = (x - b_lo) / (b_hi - b_lo)
    u_round = random_uniform(seed, counter, stream=m)
    return jnp.where(u_round < p_up, i_hi, i_lo).astype(jnp.int32)


def _rqm_block(x, seed, base_offset, params: RQMParams):
    """Shared element-wise body on a CONTIGUOUS block (used by the kernel
    and, unchanged, by the oracle in ref.py — the tiling is the only
    difference between them): element (r, c) of the block is element
    ``base_offset + r*cols + c`` of the conceptual flat input."""
    rows, cols = x.shape
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    counter = base_offset.astype(jnp.uint32) + row_ids * jnp.uint32(cols) + col_ids
    return rqm_encode_counters(x, seed, counter, params)


def _kernel(seed_ref, off_ref, x_ref, z_ref, *, params: RQMParams,
            block_rows: int):
    pid = pl.program_id(0)
    seed = seed_ref[0, 0]
    base = off_ref[0, 0] + (pid * jnp.uint32(block_rows * LANE)).astype(jnp.uint32)
    z_ref[...] = _rqm_block(x_ref[...], seed, base, params)


def rqm_quantize_2d(
    x: jnp.ndarray,
    seed: jnp.ndarray,
    params: RQMParams,
    *,
    offset,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """pallas_call entry point on a pre-tiled (rows, 128) float array.

    rows must be a multiple of block_rows; use ops.rqm for arbitrary shapes.
    seed: uint32 scalar array of shape (1, 1). offset: uint32
    scalar (may be traced) — element (r, c) draws the counter of flat
    element ``offset + r*128 + c``.
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
