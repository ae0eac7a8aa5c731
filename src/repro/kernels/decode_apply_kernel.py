"""Pallas TPU kernel: fused server-side decode + SGD apply (Algorithm 1,
lines 10-11) — the second per-coordinate hot loop of the system.

    w <- w - eta * ( -(c+delta) + 2 * z_sum * (c+delta) / (n (m-1)) )

Naively this is three HBM sweeps (decode z -> g_hat, read w, write w); the
fused kernel does one read of (w, z_sum) and one write of w per tile —
matching the RQM encode kernel's single-pass design on the other side of
the SecAgg collective. Tiled (block_rows, 128) in VMEM like the encode
kernel; the affine decode folds into two scalars computed at trace time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.grid import RQMParams

LANE = 128
DEFAULT_BLOCK_ROWS = 256


def _kernel(w_ref, z_ref, o_ref, *, scale: float, shift: float):
    """o = w - (shift + scale * z); shift/scale fold eta and the decode."""
    w = w_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    o_ref[...] = (w - (shift + scale * z)).astype(o_ref.dtype)


def decode_apply_2d(w, z_sum, params: RQMParams, n: int, lr: float,
                    *, block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: bool = False):
    """w: (rows, 128) float params; z_sum: (rows, 128) int32 SecAgg sums.
    Returns updated params (same dtype as w)."""
    rows, cols = w.shape
    if cols != LANE:
        raise ValueError(f"expected lane dim {LANE}, got {cols}")
    if rows % block_rows != 0:
        raise ValueError(f"rows {rows} not a multiple of block_rows {block_rows}")
    # g_hat = -(c+d) + z * 2(c+d)/(n(m-1));  w' = w - lr*g_hat
    scale = lr * 2.0 * params.x_max / (n * (params.m - 1))
    shift = -lr * params.x_max
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, shift=shift),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), w.dtype),
        interpret=interpret,
        name="decode_apply",
    )(w, z_sum)


def decode_apply_ref(w, z_sum, params: RQMParams, n: int, lr: float):
    """Pure-jnp oracle."""
    from repro.core.grid import decode_sum

    g_hat = decode_sum(z_sum, n, params)
    return (w.astype(jnp.float32) - lr * g_hat).astype(w.dtype)


def decode_apply(w, z_sum, params: RQMParams, n: int, lr: float,
                 *, block_rows: int | None = None,
                 interpret: bool | None = None):
    """Arbitrary-shape wrapper (flatten -> pad -> kernel -> unpad).
    block_rows=None auto-clamps to the input (ops.tile_flat)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from repro.kernels.ops import tile_flat

    shape = w.shape
    w2, nel, block_rows = tile_flat(w.reshape(-1), block_rows)
    z2, _, _ = tile_flat(z_sum.reshape(-1), block_rows)
    out = decode_apply_2d(w2, z2, params, n, lr,
                          block_rows=block_rows, interpret=interpret)
    return out.reshape(-1)[:nel].reshape(shape)


# ---------------------------------------------------------------------------
# Bit-exact fused decode + SGD apply (the fused-rounds server boundary)
# ---------------------------------------------------------------------------
#
# ``decode_apply`` above folds lr and the decode into two scalars — one
# multiply-add per element, but a DIFFERENT float association than the
# engines' decode_sum-then-sgd sequence, so it cannot serve a path whose
# contract is bit-identity. ``decode_apply_sum`` keeps the association
# exactly:  g = -x_max + z * scale;  w' = w - lr * g  — the literal ops of
# core.grid.decode_sum followed by optim.sgd, tile-streamed.


def _sum_kernel(w_ref, z_ref, o_ref, *, x_max: float, scale, lr: float):
    z = z_ref[...].astype(jnp.float32)
    g = -x_max + z * scale
    o_ref[...] = (w_ref[...] - lr * g.astype(w_ref.dtype)).astype(o_ref.dtype)


def decode_apply_sum_2d(w, z_sum, params, n: int, lr: float,
                        *, block_rows: int = DEFAULT_BLOCK_ROWS,
                        interpret: bool = False):
    """Tiled bit-exact decode+apply on a pre-tiled (rows, 128) pair.

    ``params`` is any GridGeometry (RQM / QMGeo share the affine decode);
    ``n`` must be static here — the traced-n (heterogeneous-cohort) case
    takes the jnp path in ``decode_apply_sum``."""
    rows, cols = w.shape
    if cols != LANE:
        raise ValueError(f"expected lane dim {LANE}, got {cols}")
    if rows % block_rows != 0:
        raise ValueError(f"rows {rows} not a multiple of block_rows {block_rows}")
    scale = 2.0 * params.x_max / (n * (params.m - 1))  # decode_sum's scalar
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_sum_kernel, x_max=params.x_max, scale=scale, lr=lr),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), w.dtype),
        interpret=interpret,
        name="decode_apply_sum",
    )(w, z_sum)


def decode_apply_sum(w, z_sum, params, n, lr: float,
                     *, block_rows: int | None = None,
                     interpret: bool | None = None,
                     pack_bits: int | None = None):
    """Fused SecAgg-sum decode + SGD apply, bit-identical to
    ``optim.sgd().update(grid.decode_sum(z_sum, n, params), ...)``.

    ``n`` may be traced (the heterogeneous realized cohort size) — that
    case, and every non-TPU backend, runs the same two-expression jnp
    program XLA fuses into one sweep: bit-identity BY CONSTRUCTION, the
    dispatch the engines' fused_rounds contract rides on. The Pallas tile
    kernel serves the static-n TPU path with the same float association;
    across compilation modes FMA contraction can still shift the float
    result by ~1 ULP, so cross-path tests compare it at 1-ULP tolerance
    (unlike the INTEGER round-sum kernel, which is exact everywhere).

    ``pack_bits``: ``z_sum`` is the PACKED wire-word vector the packed
    round-sum kernel emitted (core/wire.py) — consumed directly:
    unpack -> decode -> apply in one pass (the Pallas
    ``pack_kernel.unpack_decode_apply`` tile kernel on TPU/interpret, a
    single fused XLA sweep elsewhere), so the dense (dim,) int32 sum
    never lands in HBM between the collective and the parameter update.
    Unpacking is exact, so bit-identity with the unpacked path holds by
    construction."""
    from repro.core.grid import decode_sum as grid_decode_sum

    if pack_bits is not None:
        from repro.core import wire
        from repro.kernels import pack_kernel

        shape = w.shape
        w_flat = w.reshape(-1)
        words = z_sum.reshape(-1)
        pallas_ok = ((jax.default_backend() == "tpu" or interpret)
                     and isinstance(n, int))
        if pallas_ok:
            out = pack_kernel.unpack_decode_apply(
                w_flat, words, params, n, lr, pack_bits=pack_bits,
                interpret=(jax.default_backend() != "tpu"
                           if interpret is None else interpret),
            )
            if out is not None:
                return out.reshape(shape)
        z = wire.unpack_bits(words, pack_bits, w_flat.shape[0]).reshape(shape)
        g_hat = grid_decode_sum(z, n, params)
        return w - lr * g_hat.astype(w.dtype)

    pallas_ok = (jax.default_backend() == "tpu" or interpret) and isinstance(n, int)
    if not pallas_ok:
        g_hat = grid_decode_sum(z_sum, n, params)
        return w - lr * g_hat.astype(w.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    from repro.kernels.ops import tile_flat

    shape = w.shape
    w2, nel, block_rows = tile_flat(w.reshape(-1), block_rows)
    z2, _, _ = tile_flat(z_sum.reshape(-1), block_rows)
    out = decode_apply_sum_2d(w2, z2, params, n, lr,
                              block_rows=block_rows, interpret=interpret)
    return out.reshape(-1)[:nel].reshape(shape)
