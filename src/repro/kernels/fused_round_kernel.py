"""Pallas TPU kernel: the fused ROUND — clip -> encode -> shard-local sum.

The paper's aggregation never needs the per-client encoded batch: the only
quantity that crosses the SecAgg boundary is the dim-length integer level
SUM over the cohort. Every engine previously materialized the full
(cohort, dim) int32 batch just to reduce it one line later — O(cohort*dim)
peak memory and a full extra HBM round-trip. This kernel streams cohort
rows through (block_rows, 128) VMEM tiles and accumulates the per-column
level sum IN KERNEL, so peak memory is O(tile) + O(dim) regardless of the
cohort size.

Dataflow (grid = (dim/128 column blocks, rows/block_rows row blocks); the
row axis is the INNER grid dimension, so each 128-lane output block sees
its row blocks consecutively and accumulates in place — the standard
Pallas output-revisiting reduction):

    x tile (block_rows, 128) --clip/scale (compute_dtype)--> encode
        --* weight tile (int32)--> partial column sum (1, 128)
        --@pl.when(first row block) init / else +=--> z_sum block

Invariants every path must preserve (tested bit-exactly in
tests/test_fused_round_kernel.py):

  * RNG counters: element (r, c) of the conceptual (total_rows, dim)
    cohort batch draws counter ``(row_offset + r) * dim + c`` — the exact
    convention of ops.<name>_batch, so the fused sum equals
    ``encode_batch(...).sum(0)`` bit-for-bit. ``dim`` here is the TRUE
    feature width: column-padding lanes compute garbage counters, but
    their sums land in sliced-off output columns.
  * Weights: one int32 per row (0 = padded row or dropped participant,
    1 = participant). Integer multiply-then-sum is exact, so hetero
    masking inside the kernel equals masking the materialized batch.
  * Integer accumulation: int32 adds are associative — any (block_rows,
    tiling, shard) split of the sum is bit-identical to the flat sum.
  * ``compute_dtype`` only narrows the CLIP/SCALE stage (bf16 halves the
    VPU input width on TPU); the level arithmetic and the sum stay
    integer-exact.

The unfused round's materialized batch encode lives here too
(``encode_rows_2d``): the same counters over the same ``(rows, dim)``
stack, read in its own layout and written back as a ``(rows, dim)``
int32 batch, with no sum.

On CPU the same math runs as a serial ``lax.scan`` over row chunks (one
chunk's encode live at a time — measured ~16x lower XLA temp memory AND
faster than the materialized batch on this container, where XLA:CPU runs
the whole encode single-threaded anyway; see benchmarks/kernel_bench.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import wire
from repro.kernels.pbm_kernel import pbm_encode_counters
from repro.kernels.qmgeo_kernel import qmgeo_encode_counters
from repro.kernels.rqm_kernel import LANE, SUBLANE, rqm_encode_counters

DEFAULT_BLOCK_ROWS = 8  # cohort rows per VMEM tile / CPU scan chunk

ENCODERS = {
    "rqm": rqm_encode_counters,
    "pbm": pbm_encode_counters,
    "qmgeo": qmgeo_encode_counters,
}


ROWS_BLOCK_ELEMS = 16 * 1024  # elements per row-native encode block
FOLD_WIDTH = SUBLANE * LANE  # one row viewed as (n, 128) fills whole vregs


def pick_rows_block(rows: int, dim: int,
                    elems: int = ROWS_BLOCK_ELEMS) -> tuple[int, int]:
    """``(block_r, block_c)`` of the row-native encode for a ``(rows, dim)``
    batch: at most one sublane of rows (a shorter batch takes its own
    height), and about ``elems`` elements a block (the whole width where
    ``dim`` is narrower than that). Lanes come in multiples of 128; with
    fewer rows than a sublane, in multiples of ``FOLD_WIDTH`` (never
    fewer than one), so that each row of the block folds into whole
    ``(8, 128)`` vregs (``rows_block_folds``)."""
    block_r = min(rows, SUBLANE)
    unit = FOLD_WIDTH if block_r < SUBLANE else LANE
    block_c = max(unit, elems // block_r // unit * unit)
    return block_r, (dim if block_c >= dim else block_c)


def rows_block_folds(block_r: int, block_c: int) -> bool:
    """True when the row-native encode folds each row of a
    ``(block_r, block_c)`` block into a ``(block_c // 128, 128)`` value:
    fewer rows than a sublane, and a width of whole ``FOLD_WIDTH`` rows."""
    return block_r < SUBLANE and block_c % FOLD_WIDTH == 0


def _encode_rows_kernel(seed_ref, off_ref, x_ref, z_ref, *, encode, params,
                        dim: int, block_r: int, block_c: int):
    r_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_r, block_c), 0)
    c_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_r, block_c), 1)
    g_row = (off_ref[0, 0] + pl.program_id(0).astype(jnp.uint32)
             * jnp.uint32(block_r) + r_ids)
    g_col = pl.program_id(1).astype(jnp.uint32) * jnp.uint32(block_c) + c_ids
    z_ref[...] = encode(x_ref[...], seed_ref[0, 0],
                        g_row * jnp.uint32(dim) + g_col, params)


def _encode_rows_folded_kernel(seed_ref, off_ref, x_ref, z_ref, *, encode,
                               params, dim: int, block_r: int, block_c: int):
    """``_encode_rows_kernel`` with each row of the block encoded as a
    ``(block_c // 128, 128)`` value: the same counters, whole vregs."""
    shape = (block_c // LANE, LANE)
    g_row0 = (off_ref[0, 0] + pl.program_id(0).astype(jnp.uint32)
              * jnp.uint32(block_r))
    g_col = (pl.program_id(1).astype(jnp.uint32) * jnp.uint32(block_c)
             + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
             * jnp.uint32(LANE)
             + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    for r in range(block_r):
        counter = (g_row0 + jnp.uint32(r)) * jnp.uint32(dim) + g_col
        z = encode(x_ref[r:r + 1, :].reshape(shape), seed_ref[0, 0], counter,
                   params)
        z_ref[r:r + 1, :] = z.reshape(1, block_c)


def encode_rows_2d(x, seed, row_offset, encode_name: str, params, *,
                   block_elems: int = ROWS_BLOCK_ELEMS,
                   interpret: bool = False):
    """The batch encode on a ``(rows, dim)`` stack in its own layout.

    x: (rows, dim) float, any dim; seed, row_offset: uint32 scalars
    (row_offset may be traced: the shard engine's slice position).
    Returns (rows, dim) int32, element (r, c) drawn at counter
    ``(row_offset + r) * dim + c`` — the ``<name>_batch`` convention, so
    the levels equal the flat kernel's on ``x.reshape(-1)`` at element
    offset ``row_offset * dim``, bit for bit. Nothing is flattened or
    padded: the grid is ``(cdiv(rows, block_r), cdiv(dim, block_c))`` and
    the last row and column blocks are edge blocks whose out-of-range
    lanes compute garbage that the write drops.

    VMEM at the default 16K-element block (8 x 2048): the f32 input and
    int32 output blocks, double-buffered, take 4 x 64 KiB; the encode
    keeps about eight block-sized 32-bit arrays live (the clipped input,
    counters, bin index, both level accumulators, and a draw, its
    comparison and select), about 512 KiB more: some 768 KiB of the
    16 MiB scoped limit. Smaller blocks ran faster on a v5e (RQM on the
    8 x 129,385,856 stack: 69.5 ms at 16K elements, 82.7 ms at 32K,
    110.5 ms at 64K).

    Fewer rows than a sublane fold (``rows_block_folds``): such a stack
    sits in HBM tiled ``T(rows, 128)``, and a ``(2, 8192)`` value laid
    out in ``(8, 128)`` vregs fills 2 of 8 sublanes, behind relayouts
    (284 ms for RQM on the 2 x 315,062,592 stack on a v5e, 6.8x the
    8-row rate a coordinate). The folded kernel, named
    ``<name>_encode_rows_folded``, loops over the block's rows and
    encodes each as a ``(block_c // 128, 128)`` value that fills whole
    vregs, with the same counters. The block keeps the 16K-element
    budget in whole 1,024-lane rows (2 rows: 2 x 8192; 3: 3 x 5120), so
    its I/O buffers take the same 256 KiB and one row's encode about
    256 KiB live.
    """
    rows, dim = x.shape
    block_r, block_c = pick_rows_block(rows, dim, block_elems)
    grid = (pl.cdiv(rows, block_r), pl.cdiv(dim, block_c))
    folds = rows_block_folds(block_r, block_c)
    kernel = _encode_rows_folded_kernel if folds else _encode_rows_kernel
    return pl.pallas_call(
        functools.partial(kernel, encode=ENCODERS[encode_name],
                          params=params, dim=dim, block_r=block_r,
                          block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda r, c: (0, 0)),       # seed
            pl.BlockSpec((1, 1), lambda r, c: (0, 0)),       # row_offset
            pl.BlockSpec((block_r, block_c), lambda r, c: (r, c)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct((rows, dim), jnp.int32),
        interpret=interpret,
        name=f"{encode_name}_encode_rows{'_folded' if folds else ''}",
    )(seed.reshape(1, 1), row_offset.reshape(1, 1), x)


def pick_round_block_rows(rows: int, requested: int = DEFAULT_BLOCK_ROWS) -> int:
    """Clamp the row-block height to the cohort: sublane-aligned, never
    taller than the (padded) cohort itself. Cohorts are tens of rows, not
    thousands — the default keeps one tile's encode intermediates small
    while the 128-lane width fills the VPU."""
    rows_padded = -(-rows // SUBLANE) * SUBLANE
    return max(SUBLANE, min(requested, rows_padded))


def _round_sum_kernel(seed_ref, off_ref, x_ref, w_ref, o_ref, *,
                      encode, params, dim: int, block_rows: int,
                      compute_dtype):
    pid_c = pl.program_id(0)
    pid_r = pl.program_id(1)
    seed = seed_ref[0, 0]
    rows, cols = block_rows, LANE
    r_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    # global batch coordinates of this tile -> the *_batch counter
    # convention (row_offset may be traced: it arrives as an operand)
    g_row = off_ref[0, 0] + pid_r.astype(jnp.uint32) * jnp.uint32(rows) + r_ids
    g_col = pid_c.astype(jnp.uint32) * jnp.uint32(cols) + c_ids
    counter = g_row * jnp.uint32(dim) + g_col
    z = encode(x_ref[...], seed, counter, params, compute_dtype=compute_dtype)
    partial = jnp.sum(z * w_ref[...], axis=0, keepdims=True)

    @pl.when(pid_r == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(pid_r != 0)
    def _accumulate():
        o_ref[...] += partial


def round_sum_2d(x, w, seed, row_offset, encode, params, *, dim: int,
                 block_rows: int, interpret: bool = False,
                 compute_dtype=jnp.float32):
    """pallas_call entry on a pre-padded batch.

    x: (rows_p, dim_p) float, rows_p % block_rows == 0, dim_p % 128 == 0.
    w: (rows_p, 128) int32 row weights (each row's weight replicated
       across the lane so the tile multiply is a plain vreg op).
    seed, row_offset: (1, 1) uint32 scalars.
    dim: the TRUE feature width the RNG counters index (<= dim_p).
    Returns (dim_p // 128, 1, 128) int32 column sums (reshape(-1)[:dim]).
    The output is 3-D so each (1, 128) block spans the array's last two
    dimensions — the TPU block rule, which a (1, 128) block of a
    (dim_p // 128, 128) array breaks.
    """
    rows_p, dim_p = x.shape
    if dim_p % LANE:
        raise ValueError(f"dim_p {dim_p} not a multiple of lane {LANE}")
    if rows_p % block_rows:
        raise ValueError(f"rows {rows_p} not a multiple of block_rows {block_rows}")
    grid = (dim_p // LANE, rows_p // block_rows)  # row blocks INNERMOST
    return pl.pallas_call(
        functools.partial(
            _round_sum_kernel, encode=encode, params=params, dim=dim,
            block_rows=block_rows, compute_dtype=compute_dtype,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda c, r: (0, 0)),       # seed
            pl.BlockSpec((1, 1), lambda c, r: (0, 0)),       # row_offset
            pl.BlockSpec((block_rows, LANE), lambda c, r: (r, c)),
            pl.BlockSpec((block_rows, LANE), lambda c, r: (r, 0)),  # weights
        ],
        out_specs=pl.BlockSpec((None, 1, LANE), lambda c, r: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((dim_p // LANE, 1, LANE), jnp.int32),
        interpret=interpret,
        name="round_sum",
    )(seed.reshape(1, 1), row_offset.reshape(1, 1), x, w)


@functools.partial(jax.jit, static_argnames=("encode_name", "params",
                                             "block_rows", "compute_dtype"))
def round_sum_jnp(x, w, seed, row_offset, encode_name: str, params,
                  block_rows: int, compute_dtype=jnp.float32):
    """The fused round sum as a serial ``lax.scan`` over row chunks — the
    kernel's exact math on CPU, one chunk's encode intermediates live at a
    time. Bit-identical to the Pallas path and to the materialized
    ``encode_batch(...).sum(0)`` (int32 adds are associative).

    x: (rows, dim) float batch; w: (rows,) int32 row weights;
    seed/row_offset: uint32 scalars (row_offset may be traced).
    Returns the (dim,) int32 weighted column sum.
    """
    encode = ENCODERS[encode_name]
    rows, dim = x.shape
    n_chunks = -(-rows // block_rows)
    pad = n_chunks * block_rows - rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        w = jnp.pad(w, (0, pad))  # zero weight: padded rows contribute 0
    xc = x.reshape(n_chunks, block_rows, dim)
    wc = w.astype(jnp.int32).reshape(n_chunks, block_rows)
    starts = (jnp.arange(n_chunks, dtype=jnp.uint32)
              * jnp.uint32(block_rows))
    r_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, dim), 0)
    c_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, dim), 1)
    base = row_offset.astype(jnp.uint32)

    def body(acc, xs):
        x_chunk, w_chunk, start = xs
        counter = (base + start + r_ids) * jnp.uint32(dim) + c_ids
        z = encode(x_chunk, seed, counter, params,
                   compute_dtype=compute_dtype)
        z = z * w_chunk[:, None]
        return acc + jnp.sum(z, axis=0, dtype=jnp.int32), None

    acc0 = jnp.zeros((dim,), jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, (xc, wc, starts), unroll=1)
    return acc


# ---------------------------------------------------------------------------
# Packed round sum: the accumulator emits wire words directly
# ---------------------------------------------------------------------------
#
# With ``pack_bits`` set, the per-column level sum never exists as a
# dense (dim,) int32 vector: each output tile is a tile of PACKED wire
# words (core/wire.py planar layout — coordinate c lives in field
# ``c // W`` of word ``c % W``), and the grid gains a FIELD axis between
# the word-block and row-block axes. The output word block is revisited
# consecutively over (field, row block) — the same output-revisiting
# reduction as above, accumulating ``partial << (f * bits)`` per visit.
# Exact whenever no field overflows (``wire.check_packable`` upstream);
# column-padding lanes are zeroed in-kernel so the emitted words are
# CANONICAL (identical to ``wire.pack_bits`` of the unpacked sum, with
# zero pad fields — what the golden packed-word fixtures pin).


def _round_sum_packed_kernel(seed_ref, off_ref, x_ref, w_ref, o_ref, *,
                             encode, params, dim: int, words: int,
                             bits: int, block_rows: int, compute_dtype):
    pid_w = pl.program_id(0)
    pid_f = pl.program_id(1)
    pid_r = pl.program_id(2)
    seed = seed_ref[0, 0]
    rows, cols = block_rows, LANE
    r_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    g_row = off_ref[0, 0] + pid_r.astype(jnp.uint32) * jnp.uint32(rows) + r_ids
    # the TRUE flat coordinate this lane packs: field f of word block w
    g_col = (pid_f.astype(jnp.uint32) * jnp.uint32(words)
             + pid_w.astype(jnp.uint32) * jnp.uint32(cols) + c_ids)
    counter = g_row * jnp.uint32(dim) + g_col
    z = encode(x_ref[...], seed, counter, params, compute_dtype=compute_dtype)
    partial = jnp.sum(z * w_ref[...], axis=0, keepdims=True)
    # zero the column-padding lanes (coordinates >= dim): pad fields of
    # the emitted words stay 0 — the canonical wire.pack_bits layout
    partial = jnp.where(g_col[:1, :] < jnp.uint32(dim), partial, 0)
    shifted = partial << (pid_f * bits)
    first = jnp.logical_and(pid_f == 0, pid_r == 0)

    @pl.when(first)
    def _init():
        o_ref[...] = shifted

    @pl.when(jnp.logical_not(first))
    def _accumulate():
        o_ref[...] += shifted


def round_sum_packed_2d(x, w, seed, row_offset, encode, params, *,
                        dim: int, words: int, bits: int, block_rows: int,
                        interpret: bool = False, compute_dtype=jnp.float32):
    """pallas_call entry for the packed round sum on a pre-padded batch.

    x: (rows_p, fields*words) float, words % 128 == 0 (the lane-aligned
    word-count case; unaligned sizes pack the dense kernel's output
    instead — see ``round_sum``). Returns (words // 128, 1, 128) int32
    packed words (``reshape(-1)`` for the (words,) wire vector), 3-D for
    the same block rule as ``round_sum_2d``.
    """
    rows_p, dim_p = x.shape
    fields = wire.fields_per_word(bits)
    if words % LANE:
        raise ValueError(f"packed words {words} not a multiple of {LANE}")
    if dim_p != fields * words:
        raise ValueError(f"padded dim {dim_p} != fields*words "
                         f"{fields}*{words}")
    if rows_p % block_rows:
        raise ValueError(f"rows {rows_p} not a multiple of block_rows {block_rows}")
    wb = words // LANE
    grid = (wb, fields, rows_p // block_rows)  # (field, row) INNERMOST
    return pl.pallas_call(
        functools.partial(
            _round_sum_packed_kernel, encode=encode, params=params, dim=dim,
            words=words, bits=bits, block_rows=block_rows,
            compute_dtype=compute_dtype,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, f, r: (0, 0)),     # seed
            pl.BlockSpec((1, 1), lambda i, f, r: (0, 0)),     # row_offset
            pl.BlockSpec((block_rows, LANE),
                         lambda i, f, r, wb=wb: (r, f * wb + i)),
            pl.BlockSpec((block_rows, LANE), lambda i, f, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, LANE), lambda i, f, r: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((wb, 1, LANE), jnp.int32),
        interpret=interpret,
        name="round_sum_packed",
    )(seed.reshape(1, 1), row_offset.reshape(1, 1), x, w)


@functools.partial(jax.jit, static_argnames=("encode_name", "params",
                                             "block_rows", "pack_bits",
                                             "compute_dtype"))
def round_sum_packed_jnp(x, w, seed, row_offset, encode_name: str, params,
                         block_rows: int, pack_bits: int,
                         compute_dtype=jnp.float32):
    """The packed round sum as the same serial ``lax.scan`` as
    ``round_sum_jnp``, accumulating PACKED words: each chunk's dense
    partial is packed (field-wise addition distributes — pack is linear
    while no field overflows), so the carry is (words,) int32 instead of
    (dim,). Bit-identical to ``wire.pack_bits(round_sum_jnp(...))`` and
    to the Pallas packed kernel."""
    encode = ENCODERS[encode_name]
    rows, dim = x.shape
    words = wire.packed_words(dim, pack_bits)
    n_chunks = -(-rows // block_rows)
    pad = n_chunks * block_rows - rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        w = jnp.pad(w, (0, pad))
    xc = x.reshape(n_chunks, block_rows, dim)
    wc = w.astype(jnp.int32).reshape(n_chunks, block_rows)
    starts = (jnp.arange(n_chunks, dtype=jnp.uint32)
              * jnp.uint32(block_rows))
    r_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, dim), 0)
    c_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, dim), 1)
    base = row_offset.astype(jnp.uint32)

    def body(acc, xs):
        x_chunk, w_chunk, start = xs
        counter = (base + start + r_ids) * jnp.uint32(dim) + c_ids
        z = encode(x_chunk, seed, counter, params,
                   compute_dtype=compute_dtype)
        z = z * w_chunk[:, None]
        partial = jnp.sum(z, axis=0, dtype=jnp.int32)
        return acc + wire.pack_bits(partial, pack_bits, words=words), None

    acc0 = jnp.zeros((words,), jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, (xc, wc, starts), unroll=1)
    return acc


def packed_kernel_engages(dim: int, bits: int) -> bool:
    """True when the packed grid tiles ``dim``'s canonical wire words (a
    lane-aligned word count). Otherwise ``round_sum`` runs the dense
    kernel and packs its output — bit-identical, one extra pass."""
    return wire.packed_words(dim, bits) % LANE == 0


def round_sum(x, key_seed, params, encode_name: str, *, weights=None,
              row_offset=None, block_rows=None, interpret=None,
              compute_dtype=jnp.float32, pack_bits=None):
    """Arbitrary-shape fused round sum (the ops.<name>_round_sum backend).

    x: (rows, dim) stacked cohort batch; key_seed: uint32 scalar seed
    (ops.key_to_seed); weights: optional (rows,) int row weights (hetero
    participation mask — None means every row counts); row_offset:
    optional (traced) row offset into the conceptual (total_rows, dim)
    batch (the shard engine's slice position). Returns (dim,) int32 —
    or, with ``pack_bits`` set, the (ceil(dim / (32 // pack_bits)),)
    int32 PACKED wire words of that sum (canonical ``wire.pack_bits``
    layout; caller guarantees no field overflow via
    ``wire.check_packable``).
    """
    rows, dim = x.shape
    if weights is None:
        weights = jnp.ones((rows,), jnp.int32)
    offset = (jnp.zeros((), jnp.uint32) if row_offset is None
              else jnp.asarray(row_offset).astype(jnp.uint32))
    if block_rows is None:
        block_rows = pick_round_block_rows(rows)
    use_pallas = jax.default_backend() == "tpu" or interpret
    if not use_pallas:
        if pack_bits is not None:
            return round_sum_packed_jnp(x, weights, key_seed, offset,
                                        encode_name, params, block_rows,
                                        pack_bits, compute_dtype)
        return round_sum_jnp(x, weights, key_seed, offset, encode_name,
                             params, block_rows, compute_dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows_p = -(-rows // block_rows) * block_rows
    w2 = jnp.broadcast_to(
        jnp.pad(weights.astype(jnp.int32), (0, rows_p - rows))[:, None],
        (rows_p, LANE),
    )
    if pack_bits is not None:
        if packed_kernel_engages(dim, pack_bits):
            fields = wire.fields_per_word(pack_bits)
            words = wire.packed_words(dim, pack_bits)
            # columns pad to fields*words so field f of word w is column
            # f*words + w; padded coordinates are zeroed in-kernel
            x2 = jnp.pad(x, ((0, rows_p - rows), (0, fields * words - dim)))
            out = round_sum_packed_2d(
                x2, w2, key_seed, offset, ENCODERS[encode_name], params,
                dim=dim, words=words, bits=pack_bits, block_rows=block_rows,
                interpret=interpret, compute_dtype=compute_dtype,
            )
            return out.reshape(-1)
        # unaligned word count: the packed grid cannot tile canonical
        # words — run the dense kernel and pack its output (one extra
        # elementwise pass; bit-identical by pack linearity)
    dim_p = -(-dim // LANE) * LANE
    x2 = jnp.pad(x, ((0, rows_p - rows), (0, dim_p - dim)))
    out = round_sum_2d(x2, w2, key_seed, offset, ENCODERS[encode_name],
                       params, dim=dim, block_rows=block_rows,
                       interpret=interpret, compute_dtype=compute_dtype)
    dense = out.reshape(-1)[:dim]
    if pack_bits is not None:
        return wire.pack_bits(dense, pack_bits)
    return dense
