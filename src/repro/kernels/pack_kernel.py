"""Pallas TPU kernels for the dense b-bit wire codec (core/wire.py).

Three tile-streamed entries around the planar packed layout (coordinate
``c`` -> field ``c // W`` of word ``c % W``, ``k = 32 // bits`` fields
per int32 word):

  * ``pack_flat``   — z (n,) int32 levels -> (W,) packed words. The
    output word block is the REVISITED accumulator: grid (word block,
    field) with the field axis innermost, each visit OR-ing (as ``+=``
    over disjoint bit ranges) one shifted field tile into the word tile
    — the same output-revisiting reduction the fused round kernel uses.
  * ``unpack_flat`` — (W,) words -> (n,) fields. No revisiting: every
    (field, word block) writes its own output tile once.
  * ``unpack_decode_apply`` — the packed server boundary: words ->
    field -> affine decode -> SGD apply in ONE pass, so the unpacked
    (dim,) int32 sum never round-trips HBM between the SecAgg collective
    and the parameter update. Float association matches
    ``decode_apply_sum`` exactly (g = -x_max + z*scale; w' = w - lr*g).

The planar layout is what makes these kernels trivial: field ``f`` of
word block ``i`` is exactly input row block ``f*WB + i`` of the padded
level vector viewed (rows, 128) — pure tile indexing, no intra-lane
shuffles. All entries require the word count ``W`` to be lane-aligned
(``W % 128 == 0``); unaligned sizes take the jnp codec (``wire.py``),
which is bit-identical (callers fall back, tests pin equality). On CPU
the jnp codec IS the production path; ``REPRO_PALLAS_INTERPRET=1``
exercises these kernel bodies in interpret mode (CI's kernel lane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import wire
from repro.kernels.rqm_kernel import LANE, SUBLANE


def _aligned_words(n: int, bits: int):
    """(k, W) when the tight word count tiles the lane width, else None."""
    k = wire.fields_per_word(bits)
    w = wire.packed_words(n, bits)
    return (k, w) if w % LANE == 0 else None


# ---------------------------------------------------------------------------
# pack: levels -> words (output-revisiting accumulation over fields)
# ---------------------------------------------------------------------------


def _pack_kernel(z_ref, o_ref, *, bits: int):
    f = pl.program_id(1)
    field = z_ref[...] << (f * bits)

    @pl.when(f == 0)
    def _init():
        o_ref[...] = field

    @pl.when(f != 0)
    def _accumulate():
        o_ref[...] += field  # disjoint bit ranges: += is |


def pack_flat(z, bits: int, *, interpret: bool = False):
    """Pack a flat int32 level vector into packed words via the Pallas
    kernel. Requires a lane-aligned word count — returns the jnp codec's
    result (bit-identical) otherwise. Caller guarantees ``z < 2**bits``.
    """
    n = z.shape[0]
    kw = _aligned_words(n, bits)
    if kw is None:
        return wire.pack_bits(z, bits)
    k, w = kw
    wb = w // LANE
    z2 = jnp.pad(z.astype(jnp.int32), (0, k * w - n)).reshape(-1, 1, LANE)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits),
        grid=(wb, k),  # field axis INNERMOST: word block i revisits over f
        in_specs=[pl.BlockSpec((None, 1, LANE), lambda i, f: (f * wb + i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, LANE), lambda i, f: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((wb, 1, LANE), jnp.int32),
        interpret=interpret,
    )(z2)
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# unpack: words -> levels (pure scatter of field tiles, no revisiting)
# ---------------------------------------------------------------------------


def _unpack_kernel(w_ref, o_ref, *, bits: int):
    f = pl.program_id(0)
    mask = jnp.int32((1 << bits) - 1)
    # arithmetic >> sign-extends when the top field crossed the sign
    # bit; the mask restores the field exactly (same as wire.unpack_bits)
    o_ref[...] = (w_ref[...] >> (f * bits)) & mask


def unpack_flat(words, bits: int, n: int, *, interpret: bool = False):
    """Unpack ``n`` fields from packed words via the Pallas kernel (jnp
    codec fallback when the word count is not lane-aligned)."""
    w = words.shape[0]
    k = wire.fields_per_word(bits)
    if w % LANE or w != wire.packed_words(n, bits):
        return wire.unpack_bits(words, bits, n)
    wb = w // LANE
    w2 = words.astype(jnp.int32).reshape(wb, 1, LANE)
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, bits=bits),
        grid=(k, wb),
        in_specs=[pl.BlockSpec((None, 1, LANE), lambda f, i: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, LANE), lambda f, i: (f * wb + i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k * wb, 1, LANE), jnp.int32),
        interpret=interpret,
    )(w2)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# unpack -> decode -> apply: the packed fused-rounds server boundary
# ---------------------------------------------------------------------------


def _unpack_decode_apply_kernel(w_ref, z_ref, o_ref, *, x_max: float,
                                scale, lr: float, bits: int):
    f = pl.program_id(0)
    mask = jnp.int32((1 << bits) - 1)
    z = ((z_ref[...] >> (f * bits)) & mask).astype(jnp.float32)
    # the literal ops of grid.decode_sum then optim.sgd — the same float
    # association as decode_apply_kernel._sum_kernel
    g = -x_max + z * scale
    o_ref[...] = (w_ref[...] - lr * g.astype(w_ref.dtype)).astype(o_ref.dtype)


def unpack_decode_apply(w_flat, words, params, n: int, lr: float, *,
                        pack_bits: int, block_rows: int | None = None,
                        interpret: bool = False):
    """Packed SecAgg words -> updated flat params in one tile pass.

    ``w_flat``: (dim,) params; ``words``: the packed (W,) int32 sum at
    ``pack_bits`` per field; ``n`` static. Returns the updated (dim,)
    params, or None when the geometry cannot tile (caller then takes the
    fused jnp unpack+decode+apply expression, which XLA compiles to one
    sweep anyway — bit-identity either way, modulo the documented ~1 ULP
    FMA caveat across compilation modes)."""
    k = wire.fields_per_word(pack_bits)
    dim = w_flat.shape[0]
    w_cnt = words.shape[0]
    if w_cnt % LANE or w_cnt != wire.packed_words(dim, pack_bits):
        return None
    rows_w = w_cnt // LANE
    if block_rows is None:
        block_rows = SUBLANE if rows_w % SUBLANE == 0 else 1
    if rows_w % block_rows:
        return None
    scale = 2.0 * params.x_max / (n * (params.m - 1))
    wb = rows_w // block_rows
    # 3-D (blocks, block_rows, 128) views: every block spans the array's
    # last two dimensions, which the TPU block rule accepts at any
    # block_rows (a (1, 128) block of a 2-D array it refuses)
    w3 = jnp.pad(w_flat, (0, k * w_cnt - dim)).reshape(-1, block_rows, LANE)
    z3 = words.astype(jnp.int32).reshape(wb, block_rows, LANE)
    block = (None, block_rows, LANE)
    out = pl.pallas_call(
        functools.partial(_unpack_decode_apply_kernel, x_max=params.x_max,
                          scale=scale, lr=lr, bits=pack_bits),
        grid=(k, wb),
        in_specs=[
            pl.BlockSpec(block, lambda f, i: (f * wb + i, 0, 0)),
            pl.BlockSpec(block, lambda f, i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(block, lambda f, i: (f * wb + i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k * wb, block_rows, LANE),
                                       w_flat.dtype),
        interpret=interpret,
    )(w3, z3)
    return out.reshape(-1)[:dim]
