"""Bit-exactness of the row-native batch encode
(``fused_round_kernel.encode_rows_2d``) against the flat encode kernel.

The row-native kernel reads a stacked ``(rows, dim)`` batch in its own
layout; the flat path flattens it, pads it to ``(rows, 128)`` tiles and
encodes element ``i`` at counter ``offset + i``. With the element offset
``row_offset * dim`` both draw the same counters, so every mechanism must
emit the same integers, bit for bit: on sublane-aligned and unaligned row
counts, widths that are and are not whole lanes, the default block and a
small one whose last row and column blocks are edge blocks, with fewer
rows than a sublane both whole-width and folded (each row encoded as
whole ``(8, 128)`` vregs), with no row offset and with a traced one (the
shard engine's slice position). Both kernels run in interpret mode here;
``tests/test_tpu_compile.py`` compiles the row-native kernel for a
described chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grid import RQMParams
from repro.core.pbm import PBMParams
from repro.core.qmgeo import QMGeoParams
from repro.kernels import ops
from repro.kernels.fused_round_kernel import (encode_rows_2d, pick_rows_block,
                                              rows_block_folds)

PARAMS = {
    "rqm": RQMParams(c=1.0, delta=1.0, m=16, q=0.42),
    "pbm": PBMParams(c=1.0, m=16, theta=0.25),
    "qmgeo": QMGeoParams(c=1.0, delta=1.0, m=16, r=0.6),
}
FLAT_OPS = {"rqm": ops.rqm, "pbm": ops.pbm, "qmgeo": ops.qmgeo}
BATCH_OPS = {"rqm": ops.rqm_batch, "pbm": ops.pbm_batch,
             "qmgeo": ops.qmgeo_batch}
# (8, 4096): whole sublanes and lanes; (40, 1000): dim not a multiple of
# 128; (3, 300): fewer rows than a sublane, whole-width and unfolded;
# (10, 128): a partial row block; (1, 20000), (2, 20000), (3, 9000),
# (7, 5000): fewer rows than a sublane, folded, with a column edge block
SHAPES = [(8, 4096), (40, 1000), (3, 300), (10, 128),
          (1, 20_000), (2, 20_000), (3, 9_000), (7, 5_000)]
# the default block, and 1K elements: several column blocks, the last an
# edge block wherever dim is not a multiple of the block width
BLOCKS = {"default": None, "1k": 1024}
ROW_OFFSET = 5


def _batch(shape, seed):
    # span beyond [-c, c] so the in-kernel clip stage is exercised
    return jax.random.uniform(jax.random.key(seed), shape, jnp.float32,
                              -1.5, 1.5)


@pytest.mark.parametrize("block", list(BLOCKS))
@pytest.mark.parametrize("offset", ["none", "traced"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("name", list(PARAMS))
def test_rows_equal_flat(name, shape, offset, block):
    params = PARAMS[name]
    rows, dim = shape
    x = _batch(shape, seed=rows * dim)
    key = jax.random.key(11)
    row_offset = ROW_OFFSET if offset == "traced" else 0
    kw = {} if BLOCKS[block] is None else {"block_elems": BLOCKS[block]}

    @jax.jit
    def rows_kernel(x, row_offset):
        return encode_rows_2d(x, ops.key_to_seed(key),
                              row_offset.astype(jnp.uint32), name, params,
                              interpret=True, **kw)

    got = rows_kernel(x, jnp.asarray(row_offset, jnp.int32))
    flat = FLAT_OPS[name](x.reshape(-1), key, params,
                          offset=jnp.uint32(row_offset * dim))
    assert got.shape == shape and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(flat).reshape(shape))


@pytest.mark.parametrize("name", list(PARAMS))
def test_batch_dispatch_interpret_equals_twin(name, monkeypatch):
    """``<name>_batch`` under REPRO_PALLAS_INTERPRET=1 (the row-native
    kernel body) equals its default CPU dispatch (the flat jnp twin), with
    and without a shard's row offset."""
    params = PARAMS[name]
    x = _batch((24, 700), seed=3)
    key = jax.random.key(5)
    twin = [BATCH_OPS[name](x, key, params, row_offset=o) for o in (None, 7)]
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    kernel = [BATCH_OPS[name](x, key, params, row_offset=o)
              for o in (None, 7)]
    for a, b in zip(twin, kernel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# the blocks below that fold: fewer rows than a sublane, whole 1,024-lane rows
FOLDED = {(2, 8192), (3, 5120), (1, 16_384), (2, 4096), (2, 1024), (3, 1024),
          (7, 1024)}


@pytest.mark.parametrize("shape,block", [
    ((8, 129_385_856), (8, 2048)),   # the LM stack: 8-row blocks
    ((40, 222_030), (8, 2048)),      # EMNIST: an edge column block
    ((3, 300), (3, 300)),            # narrower than a block: whole batch
    ((2, 100_000), (2, 8192)),       # fewer rows than a sublane
    ((3, 100_000), (3, 5120)),       # whole 1,024-lane rows
    ((1, 100_000), (1, 16_384)),
    ((2, 315_062_592), (2, 8192)),   # the granite stack
    ((2, 4096), (2, 4096)),          # whole width, whole vregs
    ((2, 5000), (2, 5000)),          # whole width, not whole vregs
    # the 1K-element test block: never below one 1,024-lane row
    ((2, 20_000, 1024), (2, 1024)),
    ((3, 9_000, 1024), (3, 1024)),
    ((7, 5_000, 1024), (7, 1024)),
    ((40, 1000, 1024), (8, 128)),
])
def test_block_shape(shape, block):
    assert pick_rows_block(*shape) == block
    assert rows_block_folds(*block) == (block in FOLDED)
