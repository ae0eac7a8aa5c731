"""jit'd public wrappers around the Pallas kernels.

Handles arbitrary input shapes (flatten -> pad to (rows, 128) tiles ->
kernel -> slice -> reshape), key->seed derivation, interpret-mode fallback
on CPU, and pytree mapping for whole gradient trees.

Every mechanism kernel gets the same three entry points, built once by
``_make_fast_ops`` from its (pallas ``*_quantize_2d``, element-wise
``*_block``) pair:

  * ``<name>(x, key, params, *, offset, block_rows, interpret)`` — the
    Pallas path on an arbitrary-shape array (auto block sizing via
    pick_block_rows);
  * ``<name>_fast(x, key, params)`` — Pallas on TPU, the kernel's exact
    math as ONE fused jnp expression elsewhere. Bit-identical for the same
    seed (the counter-based RNG depends only on the flat element index);
    this is the hot path on CPU and what the dry-run lowers — pallas
    interpret mode would unroll its grid into a python loop, which is both
    slow and unrepresentative in compiled HLO.
  * ``<name>_batch(x, key, params, row_offset=...)`` — ``_fast`` restricted
    to a stacked ``(clients, dim)`` batch, the shape the federated round
    engine produces: one fused invocation whose RNG spans the flattened
    batch, so every client row draws independent randomness from one
    per-round seed and the output inherits the kernel<->mechanism parity
    contract on the flattened input (see kernels/ref.py).
  * ``<name>_round_sum(x, key, params, weights=..., row_offset=...,
    pack_bits=...)`` — the fused ROUND: clip -> encode -> weighted column
    sum streamed through VMEM-sized tiles (kernels/fused_round_kernel.py),
    bit-identical to ``<name>_batch(...).sum(0)`` but O(tile) instead of
    O(clients*dim) peak memory. What ``FedConfig.fused_rounds`` routes the
    engines over. With ``pack_bits`` set the accumulator emits the sum as
    bit-PACKED wire words (core/wire.py) — the dense (dim,) int32 sum
    never round-trips HBM on the packed hot path.

Shard-local batches (the "shard" round engine): when a cohort of n clients
is split across a device mesh, each shard encodes only its (n/S, dim) slice
but must draw the SAME randomness those rows would draw in the full (n, dim)
batch. ``row_offset`` (a traced scalar — it is ``axis_index * n_per`` inside
shard_map) shifts the counter-based RNG by ``row_offset * dim`` elements, so
shard-local encodes are bit-identical to the corresponding rows of the
unsharded batch encode. The offset reaches the Pallas kernels as an
operand, so on a TPU every encode — sharded or not — runs the same
compiled kernel math.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.grid import RQMParams
from repro.kernels import fused_round_kernel, pbm_kernel, qmgeo_kernel, rqm_kernel
from repro.kernels.rqm_kernel import LANE, pick_block_rows


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _force_interpret() -> bool:
    """CPU-test hook: REPRO_PALLAS_INTERPRET=1 routes the fused round-sum
    path through the Pallas kernel in interpret mode, so the kernel BODY
    (not just the fused-jnp twin) is exercised on CPU-only runners. On a
    TPU the hook is ignored: kernels there always compile."""
    return (jax.default_backend() != "tpu"
            and os.environ.get("REPRO_PALLAS_INTERPRET", "") not in ("", "0"))


def key_to_seed(key: jax.Array) -> jnp.ndarray:
    """Derive the kernel's uint32 scalar seed from a jax PRNG key."""
    return jax.random.bits(key, (), jnp.uint32)


def tile_flat(x_flat: jnp.ndarray, block_rows: int | None = None):
    """Pad a flat vector and reshape to (rows, 128) tiles.

    THE single place that derives both the (clamped) block height and the
    padding from it: ``block_rows=None`` clamps via ``pick_block_rows``,
    so every wrapper — single-leaf, batched, decode-apply — takes the same
    documented <1-sublane padding path for tiny leaves instead of
    re-deriving padding against the unclamped default. Returns
    ``(x2, n_elements, block_rows)`` with ``x2.shape[0] % block_rows == 0``.
    """
    n = x_flat.shape[0]
    if block_rows is None:
        block_rows = pick_block_rows(n)
    tile = block_rows * LANE
    padded = ((n + tile - 1) // tile) * tile
    x2 = jnp.pad(x_flat, (0, padded - n)).reshape(-1, LANE)
    return x2, n, block_rows


def _make_fast_ops(quantize_2d, block_fn, name: str):
    """Build the (pallas, fast, batch) wrapper trio for one mechanism kernel.

    quantize_2d: the pallas_call entry on a pre-tiled (rows, 128) array.
    block_fn:    the shared element-wise body (kernel == fused-jnp parity).
    """

    @functools.partial(jax.jit, static_argnames=("params", "block_rows", "interpret"))
    def _flat(x_flat, seed, offset, params, block_rows: int | None,
              interpret: bool):
        x2, n, block_rows = tile_flat(x_flat, block_rows)
        z2 = quantize_2d(x2, seed, params, offset=offset,
                         block_rows=block_rows, interpret=interpret)
        return z2.reshape(-1)[:n]

    def pallas(x, key, params, *, offset=None, block_rows=None,
               interpret=None):
        """Quantize an arbitrary-shape array via the Pallas kernel.

        offset: optional (traced) element offset into the RNG stream (see
        ``fast``). block_rows=None auto-sizes the block to the input
        (tile_flat's pick_block_rows clamp); an explicit value is honored
        as given."""
        if interpret is None:
            interpret = _interpret_default()
        seed = key_to_seed(key)
        offset = jnp.asarray(0 if offset is None else offset).astype(jnp.uint32)
        z = _flat(x.reshape(-1), seed, offset, params, block_rows, interpret)
        return z.reshape(x.shape)

    @functools.partial(jax.jit, static_argnames=("params",))
    def _flat_jnp(x_flat, seed, offset, params):
        z = block_fn(x_flat.reshape(1, -1), seed, offset, params)
        return z.reshape(-1)

    def fast(x, key, params, *, offset=None):
        """Pallas kernel on TPU, the fused jnp path elsewhere (bit-identical).

        offset: optional (traced) element offset into the counter-based RNG
        stream — element i of ``x`` draws the randomness element ``offset+i``
        of a larger flat input would draw."""
        if jax.default_backend() == "tpu":
            return pallas(x, key, params, offset=offset)
        seed = key_to_seed(key)
        offset = jnp.asarray(0 if offset is None else offset).astype(jnp.uint32)
        return _flat_jnp(x.reshape(-1), seed, offset, params).reshape(x.shape)

    def batch(x, key, params, *, row_offset=None):
        """Kernel-backed encode for a stacked ``(clients, dim)`` batch.

        row_offset: optional (traced) row offset — this batch plays rows
        ``[row_offset, row_offset + clients)`` of a larger stacked batch
        encoded with the same key (the shard engine's per-shard slice)."""
        if x.ndim != 2:
            raise ValueError(f"{name}_batch expects (clients, dim), got {x.shape}")
        offset = None
        if row_offset is not None:
            offset = (jnp.asarray(row_offset).astype(jnp.uint32)
                      * jnp.uint32(x.shape[1]))
        return fast(x, key, params, offset=offset)

    pallas.__name__, fast.__name__, batch.__name__ = (
        name, f"{name}_fast", f"{name}_batch")
    return pallas, fast, batch


def _make_round_sum(encode_name: str):
    """Build ``<name>_round_sum`` — the fused clip->encode->sum entry for a
    stacked ``(clients, dim)`` cohort batch (kernels/fused_round_kernel.py):
    bit-identical to ``<name>_batch(...).sum(axis=0)`` with the optional
    participation ``weights`` applied, but never materializing the
    (clients, dim) encoded batch. Routing mirrors ``fast``: Pallas on TPU,
    the serial-scan jnp twin elsewhere; REPRO_PALLAS_INTERPRET=1 forces
    the Pallas body in interpret mode (CI's CPU kernel lane)."""

    def round_sum(x, key, params, *, weights=None, row_offset=None,
                  block_rows=None, interpret=None,
                  compute_dtype=jnp.float32, pack_bits=None):
        if x.ndim != 2:
            raise ValueError(
                f"{encode_name}_round_sum expects (clients, dim), got {x.shape}"
            )
        if interpret is None and _force_interpret():
            interpret = True
        return fused_round_kernel.round_sum(
            x, key_to_seed(key), params, encode_name, weights=weights,
            row_offset=row_offset, block_rows=block_rows,
            interpret=interpret, compute_dtype=compute_dtype,
            pack_bits=pack_bits,
        )

    round_sum.__name__ = f"{encode_name}_round_sum"
    return round_sum


rqm, rqm_fast, rqm_batch = _make_fast_ops(
    rqm_kernel.rqm_quantize_2d, rqm_kernel._rqm_block, "rqm")
pbm, pbm_fast, pbm_batch = _make_fast_ops(
    pbm_kernel.pbm_quantize_2d, pbm_kernel._pbm_block, "pbm")
qmgeo, qmgeo_fast, qmgeo_batch = _make_fast_ops(
    qmgeo_kernel.qmgeo_quantize_2d, qmgeo_kernel._qmgeo_block, "qmgeo")

rqm_round_sum = _make_round_sum("rqm")
pbm_round_sum = _make_round_sum("pbm")
qmgeo_round_sum = _make_round_sum("qmgeo")


def rqm_tree(tree, key: jax.Array, params: RQMParams, **kw):
    """Apply RQM leaf-wise to a gradient pytree with independent seeds."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = [rqm(leaf, k, params, **kw) for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)
