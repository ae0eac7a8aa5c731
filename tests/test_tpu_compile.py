"""Every Pallas kernel of the round, compiled for a described TPU v5e.

Interpret mode and the jnp twins cannot see what the TPU compiler
refuses: blocks that break the (8, 128) tiling rule, casts Mosaic has no
lowering for, more VMEM than a kernel may hold. These tests compile each
kernel with the installed TPU compiler against a ``v5e:2x2`` topology
that is described, not attached, at the widths the lanes run:

  * the EMNIST CNN at the paper cohort: 40 clients x dim 222,030;
  * the federated LM lane: 8 clients x dim 1,096,032;
  * the benchmark's mamba2-370m cohort: 8 clients x dim 129,385,856
    (the row-native batch encode);
  * the benchmark's granite-4.0-h-micro cohort: 2 silos x dim 315,062,592
    (the row-native batch encode at two rows);
  * the packed wire kernels at a lane-aligned word count (10-bit fields,
    74,112 words — the EMNIST word count rounded up to whole lanes).

Each test asserts the compiled program holds the kernel as a
``tpu_custom_call`` under the ``name`` its ``pallas_call`` gives, which is
what a device trace calls the op. The topology is described inside a
module fixture, never at import (one process may load the TPU library at
a time; under pytest-xdist only the worker given this file loads it). The persistent
compilation cache is off here: a compile for a described chip cannot be
read back without one.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import wire
from repro.core.grid import RQMParams
from repro.core.pbm import PBMParams
from repro.core.qmgeo import QMGeoParams
from repro.kernels import decode_apply_kernel, fused_round_kernel, pack_kernel
from repro.kernels.ops import tile_flat
from repro.kernels.pbm_kernel import pbm_quantize_2d
from repro.kernels.qmgeo_kernel import qmgeo_quantize_2d
from repro.kernels.rqm_kernel import LANE, pick_block_rows, rqm_quantize_2d

EMNIST = (40, 222_030)     # paper cohort x EMNIST CNN dim
LM = (8, 1_096_032)        # lm lane cohort x its model dim
MAMBA2 = (8, 129_385_856)  # the benchmark's mamba2-370m cohort x dim
GRANITE = (2, 315_062_592)  # the benchmark's granite-4.0-h-micro cohort x dim
BITS = 10                  # wire width of a 40-client RQM m=16 sum
ALIGNED_DIM = 3 * 74_112 - 2    # -> 74,112 packed words = 579 lanes
ALIGNED8_DIM = 3 * 73_728       # -> 576 lanes: 8-row unpack blocks

RQM = RQMParams(c=0.02, delta=0.02, m=16, q=0.42)
ENCODERS = {
    "rqm": (rqm_quantize_2d, RQM),
    "pbm": (pbm_quantize_2d, PBMParams(c=0.02, m=16, theta=0.25)),
    "qmgeo": (qmgeo_quantize_2d, QMGeoParams(c=0.02, delta=0.02, m=16,
                                             r=0.6)),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` -> an argument shape on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args, kernel: str):
    """Compile ``fn`` for the described chip; every Pallas kernel in the
    compiled program must be the one named ``kernel`` (the instruction
    takes the ``pallas_call``'s ``name``, which a device trace shows)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, "no Pallas kernel in the program"
    for line in calls:
        instr = line.split(" = ", 1)[0].split()[-1].lstrip("%")
        assert re.fullmatch(rf"{kernel}(\.\d+)?", instr), line[:160]


@pytest.mark.parametrize("name", sorted(ENCODERS))
def test_encode_kernel(spec, name):
    """The flat encode of a 1-D leaf, at the paper cohort's size: the
    (40, 222030) elements flattened into (rows, 128) tiles, with a traced
    element offset."""
    quantize_2d, params = ENCODERS[name]
    n = EMNIST[0] * EMNIST[1]
    block_rows = pick_block_rows(n)

    def encode(x, seed, offset):
        x2, _, _ = tile_flat(x.reshape(-1), block_rows)
        return quantize_2d(x2, seed, params, offset=offset,
                           block_rows=block_rows)

    _compile(encode, spec(EMNIST, jnp.float32), spec((1, 1), jnp.uint32),
             spec((), jnp.uint32), kernel=f"{name}_encode")


@pytest.mark.parametrize("shape", [EMNIST, LM, MAMBA2, GRANITE],
                         ids=["emnist", "lm", "mamba2", "granite"])
@pytest.mark.parametrize("name", sorted(ENCODERS))
def test_encode_rows_kernel(spec, name, shape):
    """The materialized batch encode as the engines run it on the chip:
    the (clients, dim) stack in its own layout, an edge column block
    where dim is not a multiple of the block width, and a traced row
    offset (a shard's slice of a larger cohort)."""
    params = ENCODERS[name][1]

    def encode(x, seed, row_offset):
        return fused_round_kernel.encode_rows_2d(x, seed, row_offset, name,
                                                 params)

    _compile(encode, spec(shape, jnp.float32), spec((), jnp.uint32),
             spec((), jnp.uint32), kernel=f"{name}_encode_rows")


@pytest.mark.parametrize("shape", [EMNIST, LM], ids=["emnist", "lm"])
def test_round_sum(spec, shape):
    """The fused clip->encode->sum kernel (fused_rounds=True, dense)."""
    rows, dim = shape
    block_rows = fused_round_kernel.pick_round_block_rows(rows)
    rows_p = -(-rows // block_rows) * block_rows
    dim_p = -(-dim // LANE) * LANE
    fn = functools.partial(
        fused_round_kernel.round_sum_2d, encode=fused_round_kernel.ENCODERS["rqm"],
        params=RQM, dim=dim, block_rows=block_rows)
    _compile(fn, spec((rows_p, dim_p), jnp.float32),
             spec((rows_p, LANE), jnp.int32),
             spec((1, 1), jnp.uint32), spec((1, 1), jnp.uint32),
             kernel="round_sum")


def test_round_sum_packed(spec):
    """The packed fused round sum at a lane-aligned word count."""
    rows = EMNIST[0]
    words = wire.packed_words(ALIGNED_DIM, BITS)
    assert fused_round_kernel.packed_kernel_engages(ALIGNED_DIM, BITS)
    fields = wire.fields_per_word(BITS)
    block_rows = fused_round_kernel.pick_round_block_rows(rows)
    fn = functools.partial(
        fused_round_kernel.round_sum_packed_2d,
        encode=fused_round_kernel.ENCODERS["rqm"], params=RQM,
        dim=ALIGNED_DIM, words=words, bits=BITS, block_rows=block_rows)
    _compile(fn, spec((rows, fields * words), jnp.float32),
             spec((rows, LANE), jnp.int32),
             spec((1, 1), jnp.uint32), spec((1, 1), jnp.uint32),
             kernel="round_sum_packed")


def test_pack_flat(spec):
    _compile(lambda z: pack_kernel.pack_flat(z, BITS),
             spec((ALIGNED_DIM,), jnp.int32), kernel="pack_words")


def test_unpack_flat(spec):
    words = wire.packed_words(ALIGNED_DIM, BITS)
    _compile(lambda w: pack_kernel.unpack_flat(w, BITS, ALIGNED_DIM),
             spec((words,), jnp.int32), kernel="unpack_words")


def test_decode_apply_sum(spec):
    """The fused server boundary (dense sum) at the EMNIST dim."""
    dim = EMNIST[1]
    block_rows = pick_block_rows(dim)

    def apply(w, z):
        w2, _, _ = tile_flat(w, block_rows)
        z2, _, _ = tile_flat(z, block_rows)
        return decode_apply_kernel.decode_apply_sum_2d(
            w2, z2, RQM, EMNIST[0], 0.5, block_rows=block_rows)

    _compile(apply, spec((dim,), jnp.float32), spec((dim,), jnp.int32),
             kernel="decode_apply_sum")


def test_decode_apply(spec):
    """The folded decode+apply kernel (two scalars a tile) at the EMNIST
    dim."""
    dim = EMNIST[1]
    block_rows = pick_block_rows(dim)

    def apply(w, z):
        w2, _, _ = tile_flat(w, block_rows)
        z2, _, _ = tile_flat(z, block_rows)
        return decode_apply_kernel.decode_apply_2d(
            w2, z2, RQM, EMNIST[0], 0.5, block_rows=block_rows)

    _compile(apply, spec((dim,), jnp.float32), spec((dim,), jnp.int32),
             kernel="decode_apply")


@pytest.mark.parametrize("dim", [ALIGNED_DIM, ALIGNED8_DIM],
                         ids=["1-row-blocks", "8-row-blocks"])
def test_unpack_decode_apply(spec, dim):
    """The packed server boundary: 579 word rows take 1-row blocks, 576
    take 8-row blocks."""
    words = wire.packed_words(dim, BITS)

    def apply(w, words_):
        out = pack_kernel.unpack_decode_apply(w, words_, RQM, EMNIST[0], 0.5,
                                              pack_bits=BITS)
        assert out is not None, "geometry fell back to the jnp codec"
        return out

    _compile(apply, spec((dim,), jnp.float32), spec((words,), jnp.int32),
             kernel="unpack_decode_apply")
