"""Production meshes (TPU v5e target).

Functions, not module constants: importing this module never touches jax
device state. The dry-run sets XLA_FLAGS for 512 host devices BEFORE
importing jax; smoke tests and benches see the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

V5E = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bandwidth": 819e9,     # bytes/s per chip
    "ici_link_bandwidth": 50e9, # bytes/s per link
    "hbm_bytes": 16 * 1024**3,
}


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the repo's shard_map code assumes
    Auto, and jax.make_mesh defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_shard_mesh(shards: int = None):
    """1-D ('shard',) mesh for the federated "shard" round engine: each
    device (or fake host device) is one cohort shard, no model axis.
    shards=None uses every visible device. A 1-shard mesh is always
    buildable and is the engine's scan-equivalent degenerate case."""
    if shards is None:
        shards = jax.device_count()
    if shards > jax.device_count():
        raise ValueError(
            f"shard mesh wants {shards} devices, have {jax.device_count()} "
            f"(on CPU export XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{shards} before importing jax)"
        )
    return make_mesh((shards,), ("shard",))


def make_fed_mesh(shards: int, model_shards: int):
    """2-D ('shard', 'model') mesh for the federated shard engine with
    tensor-parallel clients (FedConfig.model_shards > 1): the 'shard'
    axis carries the cross-client integer SecAgg sum, the 'model' axis
    Megatron-style tensor parallelism INSIDE each client's gradient
    (docs/lm_federated.md). Needs shards * model_shards devices."""
    want = shards * model_shards
    if want > jax.device_count():
        raise ValueError(
            f"fed mesh wants {shards}x{model_shards}={want} devices, have "
            f"{jax.device_count()} (on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={want} before "
            f"importing jax)"
        )
    return make_mesh((shards, model_shards), ("shard", "model"))


def client_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")
