"""Distributed train / serve steps: the paper's Algorithm 1 on a TPU mesh.

The whole step runs inside one FULL-MANUAL shard_map over the mesh:

  * the ('pod','data') axes are the FEDERATED CLIENTS: each client group
    computes its own gradient on its batch shard;
  * the 'model' axis is Megatron-style tensor parallelism inside each
    client (explicit psums in the layers, grad sync per Meta.sync);
  * the paper's pipeline grad -> clip -> RQM-encode -> SecAgg-sum -> decode
    maps to: jax.grad -> per-coordinate clip -> randomized quantization
    (int32 levels) -> psum over the client axes -> affine decode. The psum
    of integer levels IS the SecAgg aggregation — the only cross-client
    collective in the step.

Beyond-paper option (packed=True): levels are packed two-per-int32 lane
(core.secagg) before the client psum, halving the RQM collective bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core import secagg, wire
from repro.core.mechanisms import Mechanism
from repro.models import meta as meta_lib
from repro.models import model as model_lib
from repro.models.common import ParallelCtx
from repro.optim import Optimizer


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Binding of mesh axes to roles.

    model_axis=None (or an axis the mesh doesn't have) is a pure
    client-parallel plan — every device is a whole client group, tp == 1.
    The federated "shard" engine (fed/engines.py) runs on exactly this plan
    over a 1-D ("shard",) mesh."""

    mesh: Mesh
    client_axes: tuple[str, ...]  # ('pod','data'), ('data',) or ('shard',)
    model_axis: Optional[str] = "model"

    @property
    def tp(self) -> int:
        if self.model_axis is None or self.model_axis not in self.mesh.shape:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def n_clients(self) -> int:
        n = 1
        for a in self.client_axes:
            n *= self.mesh.shape[a]
        return n

    def ctx(self, *, seq_parallel: bool = False) -> ParallelCtx:
        return ParallelCtx(
            model_axis=self.model_axis,
            tp=self.tp,
            client_axes=self.client_axes,
            n_clients=self.n_clients,
            seq_axis=self.client_axes or None,
            seq_axis_sizes=tuple(self.mesh.shape[a] for a in self.client_axes),
            seq_shards=self.n_clients,
            seq_parallel=seq_parallel,
        )


def round_privacy(mech: Mechanism, n_clients: int,
                  alphas=(2.0, 4.0, 8.0, 16.0, 32.0)) -> dict[float, float]:
    """Per-step aggregate-level Renyi eps of the mesh train step, queried
    from the self-accounting mechanism (Mechanism API v2). The mesh client
    axes play the federated clients, so one train step releases exactly one
    mechanism round over ``n_clients`` participants; the launcher composes
    these additively across steps (RDP composition)."""
    return {float(a): float(mech.per_round_epsilon(n_clients, a)) for a in alphas}


def _client_key(key, ctx: ParallelCtx):
    """Distinct randomness per client, identical across the model axis (so
    replicated leaves decode identical updates on every model shard)."""
    if ctx.client_axes:
        for a in ctx.client_axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
    return key


def _shard_seed_index(ctx: ParallelCtx, sync: int) -> jnp.ndarray:
    """Seed-folding index on the model axis: distinct per shard for sharded
    leaves (independent per-coordinate randomness), shared within a dup
    group / across the axis for duplicated / replicated leaves (identical
    levels -> copies stay in sync)."""
    if ctx.model_axis is None or ctx.tp == 1:
        return jnp.int32(0)
    mi = jax.lax.axis_index(ctx.model_axis)
    g = max(1, min(sync, ctx.tp))
    return mi // g


def encode_aggregate_decode(grads, meta_tree, mech: Mechanism, ctx: ParallelCtx,
                            key, *, packed: bool = False,
                            agg_dtype: str = "int32"):
    """clip -> mechanism encode -> SecAgg psum over clients -> decode.

    agg_dtype: width of the levels on the wire — "int32" (paper-faithful
    emulation), "int16" (beyond-paper: halves the SecAgg collective; safe
    while n_clients * (m-1) < 2^15), or "auto" (narrowest safe width).
    Returns the decoded aggregated gradient tree (mean over clients).
    """
    n = max(1, ctx.n_clients)
    if agg_dtype == "auto":
        agg_dtype = "int16" if mech.sum_bound(n) < (1 << 15) else "int32"
    if agg_dtype == "int16" and mech.sum_bound(n) >= (1 << 15):
        raise ValueError(f"int16 aggregation unsafe: bound {mech.sum_bound(n)}")
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    metas = jax.tree_util.tree_leaves(meta_tree, is_leaf=meta_lib.is_meta)
    assert len(leaves) == len(metas), (len(leaves), len(metas))
    out = []
    for i, (g, m) in enumerate(zip(leaves, metas)):
        leaf_key = jax.random.fold_in(key, i)
        leaf_key = jax.random.fold_in(leaf_key, _shard_seed_index(ctx, m.sync))
        z = mech.quantize(g, leaf_key)  # shared clip->encode dispatch
        if mech.name == "none":
            agg = ctx.psum_clients(z)
        elif packed:
            # the shared packing-safety gate + minimal-width codec
            # (core/wire.py): fields as narrow as the bound allows, not
            # fixed 16-bit halves
            wire.check_packable(mech.sum_bound(n), where="packed=True: ")
            flat = z.reshape(-1)
            if ctx.client_axes:
                flat = secagg.secure_sum_bounded(
                    flat, ctx.client_axes, mech.sum_bound(n), packed=True
                )
            agg = flat.reshape(z.shape)
        elif agg_dtype == "int16":
            agg = ctx.psum_clients(z.astype(jnp.int16)).astype(jnp.int32)
        else:
            agg = ctx.psum_clients(z)
        out.append(mech.decode_sum(agg, n).astype(g.dtype).reshape(g.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def _client_scatter_sum(x_flat, ctx: ParallelCtx):
    """Reduce-scatter a flat vector over the client axes (dim 0, tiled):
    the ZeRO-1 form of the SecAgg sum — each client ends with the summed
    levels of ITS master shard only."""
    for a in ctx.client_axes:
        x_flat = jax.lax.psum_scatter(x_flat, a, scatter_dimension=0, tiled=True)
    return x_flat


def _client_all_gather(x_flat, ctx: ParallelCtx):
    for a in reversed(ctx.client_axes):
        x_flat = jax.lax.all_gather(x_flat, a, axis=0, tiled=True)
    return x_flat


def _local_shape(m: meta_lib.Meta, tp: int):
    """Per-model-shard shape of a leaf (model dim divided by tp)."""
    mdim = next((i for i, e in enumerate(m.pspec) if e == "model"), None)
    if mdim is None or tp == 1:
        return tuple(m.shape)
    s = list(m.shape)
    s[mdim] //= tp
    return tuple(s)


def zero1_master_meta(meta_tree, tp: int, n_clients: int, client_axes):
    """Meta tree for the f32 master copies: per MODEL shard (dim 0, so no
    cross-model reshuffling is ever needed), flat and sharded over the
    client axes (dim 1) — the ZeRO-1 partition."""

    def leaf(m: meta_lib.Meta):
        n_local = int(np.prod(_local_shape(m, tp)))
        pad = (n_local + n_clients - 1) // n_clients * n_clients
        return meta_lib.Meta((tp, pad), jnp.float32, P("model", client_axes), 0)

    return meta_lib.tree_map(leaf, meta_tree)


def zero1_init_master(params, meta_tree, tp: int, n_clients: int):
    """Build the GLOBAL master tree from GLOBAL params (host-side helper)."""

    def leaf(p, m: meta_lib.Meta):
        mdim = next((i for i, e in enumerate(m.pspec) if e == "model"), None)
        if mdim is None or tp == 1:
            blocks = [p] * tp
        else:
            blocks = jnp.split(p, tp, axis=mdim)
        flats = []
        for b in blocks:
            f = b.astype(jnp.float32).reshape(-1)
            pad = (f.size + n_clients - 1) // n_clients * n_clients
            flats.append(jnp.pad(f, (0, pad - f.size)))
        return jnp.stack(flats)

    return meta_lib.tree_map(lambda m, p: leaf(p, m), meta_tree, params)


def build_zero1_train_step_fn(cfg: ModelConfig, mech: Mechanism, lr_fn,
                              ctx: ParallelCtx, *, remat: bool = True,
                              compute_dtype=jnp.bfloat16,
                              agg_dtype: str = "auto"):
    """ZeRO-1 variant (§Perf): bf16 compute params replicated over clients;
    f32 master (+optimizer moments if added) FLAT-SHARDED over the client
    axes. The SecAgg sum becomes a reduce-scatter of integer levels (same
    semantics: each shard decodes the sum for its slice), the updated master
    shard is cast to bf16 and all-gathered back. Per-device optimizer/master
    memory drops by n_clients; collective bytes trade an all-reduce(levels)
    for reduce-scatter(levels) + all-gather(bf16 params).

    Signature matches build_train_step_fn with opt_state == {"master": tree}.
    """
    meta_tree = model_lib.param_meta(cfg, tp=ctx.tp, dtype=compute_dtype)
    n = max(1, ctx.n_clients)
    if agg_dtype == "auto":
        agg_dtype = "int16" if mech.sum_bound(n) < (1 << 15) else "int32"

    def train_step(params, opt_state, step, batch, key):
        key = _client_key(key, ctx)
        master = opt_state["master"]

        def loss(p):
            total, aux = model_lib.loss_fn(
                p, cfg, ctx, batch, remat=remat, compute_dtype=compute_dtype
            )
            return total / ctx.tp, aux  # psum self-transpose correction

        (total, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
        total = total * ctx.tp
        grads = meta_lib.sync_grads(grads, meta_tree, ctx)

        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        m_leaves = jax.tree_util.tree_leaves(master)
        metas = jax.tree_util.tree_leaves(meta_tree, is_leaf=meta_lib.is_meta)
        lr = lr_fn(step)
        new_params, new_master = [], []
        for i, (g, mast, m) in enumerate(zip(g_leaves, m_leaves, metas)):
            # g: LOCAL leaf (model-sliced); mast: (1, pad/n) local master shard
            mast = jnp.squeeze(mast, 0)
            leaf_key = jax.random.fold_in(key, i)
            leaf_key = jax.random.fold_in(leaf_key, _shard_seed_index(ctx, m.sync))
            z = mech.quantize(g, leaf_key).reshape(-1)
            pad = mast.size * n - z.size
            z = jnp.pad(z, (0, pad))
            if mech.name != "none" and agg_dtype == "int16":
                z_shard = _client_scatter_sum(z.astype(jnp.int16), ctx)
                z_shard = z_shard.astype(jnp.int32)
            else:
                z_shard = _client_scatter_sum(z, ctx)
            ghat = mech.decode_sum(z_shard, n)
            mast_new = mast - lr * ghat
            w = _client_all_gather(mast_new.astype(compute_dtype), ctx)
            local_shape = _local_shape(m, ctx.tp)
            new_params.append(w[: int(np.prod(local_shape))].reshape(local_shape))
            new_master.append(mast_new[None])
        params_new = jax.tree_util.tree_unflatten(treedef, new_params)
        master_new = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(master), new_master
        )
        metrics = {
            "loss": ctx.pmean_clients(total),
            "ce_loss": ctx.pmean_clients(aux["ce_loss"]),
            "moe_aux_loss": ctx.pmean_clients(aux["moe_aux_loss"]),
        }
        return params_new, {"master": master_new}, metrics

    return train_step


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def build_train_step_fn(cfg: ModelConfig, mech: Mechanism, opt: Optimizer,
                        lr_fn, ctx: ParallelCtx, *, remat: bool = True,
                        compute_dtype=jnp.bfloat16, packed: bool = False,
                        agg_dtype: str = "int32"):
    """The per-shard body (runs inside shard_map, or locally with ctx()=1)."""
    meta_tree = model_lib.param_meta(cfg, tp=ctx.tp)

    def train_step(params, opt_state, step, batch, key):
        key = _client_key(key, ctx)

        def loss(p):
            total, aux = model_lib.loss_fn(
                p, cfg, ctx, batch, remat=remat, compute_dtype=compute_dtype
            )
            # psum self-transpose correction: under manual shard_map
            # (check_vma=False) the transpose of psum is psum, so the
            # replicated loss region injects one global factor of tp into
            # every cotangent path that crosses a model-axis psum.
            # Differentiating loss/tp cancels it; leaves whose paths avoid
            # all psums (replicated params, e.g. the router) end up at
            # true/tp and are restored by their sync=tp psum in sync_grads.
            return total / ctx.tp, aux

        (total, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
        total = total * ctx.tp
        grads = meta_lib.sync_grads(grads, meta_tree, ctx)  # TP corrections
        ghat = encode_aggregate_decode(
            grads, meta_tree, mech, ctx, key, packed=packed,
            agg_dtype=agg_dtype,
        )
        params, opt_state = opt.update(ghat, opt_state, params, lr_fn(step))
        metrics = {
            "loss": ctx.pmean_clients(total),
            "ce_loss": ctx.pmean_clients(aux["ce_loss"]),
            "moe_aux_loss": ctx.pmean_clients(aux["moe_aux_loss"]),
        }
        return params, opt_state, metrics

    return train_step


def make_train_step(cfg: ModelConfig, plan: MeshPlan, mech: Mechanism,
                    opt: Optimizer, lr_fn, shape: InputShape, *,
                    remat: bool = True, compute_dtype=jnp.bfloat16,
                    packed: bool = False, param_dtype=jnp.float32,
                    seq_parallel: bool | None = None,
                    sp_compress: bool = False, agg_dtype: str = "int32",
                    zero1: bool = False):
    """jit-wrapped shard_map train step + the input/param specs to call it.

    Returns (step_fn, specs) where specs is a dict of Meta trees / pspecs
    for params, opt_state and batch — the launcher uses them both to build
    ShapeDtypeStructs for the dry-run and shardings for real runs.
    """
    if seq_parallel is None:
        seq_parallel = plan.tp > 1 and shape.seq_len % plan.tp == 0
    ctx = plan.ctx(seq_parallel=seq_parallel)
    if sp_compress:
        ctx = dataclasses.replace(ctx, sp_compress=True)
    if zero1:
        if opt.name != "sgd":
            raise NotImplementedError("zero1 currently pairs with sgd")
        body = build_zero1_train_step_fn(
            cfg, mech, lr_fn, ctx, remat=remat,
            compute_dtype=compute_dtype, agg_dtype=agg_dtype,
        )
        meta_tree = model_lib.param_meta(cfg, tp=ctx.tp, dtype=compute_dtype)
        opt_meta = {"master": zero1_master_meta(
            meta_tree, plan.tp, plan.n_clients, plan.client_axes)}
    else:
        body = build_train_step_fn(
            cfg, mech, opt, lr_fn, ctx, remat=remat,
            compute_dtype=compute_dtype, packed=packed, agg_dtype=agg_dtype,
        )
        meta_tree = model_lib.param_meta(cfg, tp=ctx.tp, dtype=param_dtype)
        opt_meta = opt.state_meta(meta_tree)

    batch_specs = {
        "tokens": P(plan.client_axes, None),
        "labels": P(plan.client_axes, None),
    }
    if cfg.frontend is not None:
        batch_specs["prefix_embeds"] = P(plan.client_axes, None, None)

    param_specs = meta_lib.pspecs(meta_tree)
    opt_specs = meta_lib.pspecs(opt_meta) if opt_meta else ()

    metric_specs = {k: P() for k in ("loss", "ce_loss", "moe_aux_loss")}
    mapped = jax.shard_map(
        body,
        mesh=plan.mesh,
        in_specs=(param_specs, opt_specs, P(), batch_specs, P()),
        out_specs=(param_specs, opt_specs, metric_specs),
        check_vma=False,
    )
    specs = {
        "param_meta": meta_tree,
        "opt_meta": opt_meta,
        "batch_specs": batch_specs,
        "param_specs": param_specs,
        "opt_specs": opt_specs,
    }
    return jax.jit(mapped, donate_argnums=(0, 1)), specs


def batch_structs(cfg: ModelConfig, shape: InputShape):
    """ShapeDtypeStructs for one global training batch of `shape`."""
    B, S = shape.global_batch, shape.seq_len
    Pfx = cfg.frontend.prefix_len if cfg.frontend else 0
    out = {
        "tokens": jax.ShapeDtypeStruct((B, S - Pfx), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    if cfg.frontend is not None:
        out["prefix_embeds"] = jax.ShapeDtypeStruct((B, Pfx, cfg.d_model), jnp.bfloat16)
    return out


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_decode_step(cfg: ModelConfig, plan: MeshPlan, shape: InputShape, *,
                     compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                     kv_quant: bool = False):
    """One-token decode step against a `shape.seq_len` KV cache."""
    ctx = plan.ctx()
    seq_sharded = shape.global_batch == 1
    meta_tree = model_lib.param_meta(cfg, tp=ctx.tp, dtype=param_dtype)
    cache_meta = model_lib.cache_meta(
        cfg, ctx.tp, shape, plan.client_axes, dtype=compute_dtype,
        kv_quant=kv_quant,
    )

    def body(params, caches, tokens, pos):
        return model_lib.decode_step(
            params, caches, cfg, ctx, tokens, pos,
            seq_sharded=seq_sharded, compute_dtype=compute_dtype,
        )

    param_specs = meta_lib.pspecs(meta_tree)
    cache_specs = meta_lib.pspecs(cache_meta)
    tok_spec = P(None if seq_sharded else plan.client_axes, None)
    out_tok_spec = P(None if seq_sharded else plan.client_axes)

    mapped = jax.shard_map(
        body,
        mesh=plan.mesh,
        in_specs=(param_specs, cache_specs, tok_spec, P()),
        out_specs=(out_tok_spec, cache_specs),
        check_vma=False,
    )
    specs = {
        "param_meta": meta_tree,
        "cache_meta": cache_meta,
        "param_specs": param_specs,
        "cache_specs": cache_specs,
        "token_spec": tok_spec,
    }
    return jax.jit(mapped, donate_argnums=(1,)), specs


def make_prefill_step(cfg: ModelConfig, plan: MeshPlan, shape: InputShape, *,
                      compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                      seq_parallel: bool = False, sp_compress: bool = False):
    """Prefill a `shape.seq_len` prompt, producing caches + first token.
    seq_parallel/sp_compress: §Perf options (residual sharded over the model
    axis; int8-compressed entry gathers)."""
    if seq_parallel and shape.seq_len % plan.tp != 0:
        seq_parallel = False
    ctx = plan.ctx(seq_parallel=seq_parallel)
    if sp_compress:
        ctx = dataclasses.replace(ctx, sp_compress=True)
    meta_tree = model_lib.param_meta(cfg, tp=ctx.tp, dtype=param_dtype)

    param_specs = meta_lib.pspecs(meta_tree)
    tok_spec = P(plan.client_axes, None)
    cache_meta = model_lib.cache_meta(
        cfg, ctx.tp, shape, plan.client_axes, dtype=compute_dtype
    )
    cache_specs = meta_lib.pspecs(cache_meta)

    if cfg.frontend is not None:

        def body(params, tokens, prefix_embeds):
            return model_lib.prefill(
                params, cfg, ctx, tokens, shape,
                prefix_embeds=prefix_embeds, compute_dtype=compute_dtype,
            )

        in_specs = (param_specs, tok_spec, P(plan.client_axes, None, None))
    else:

        def body(params, tokens):
            return model_lib.prefill(
                params, cfg, ctx, tokens, shape, compute_dtype=compute_dtype,
            )

        in_specs = (param_specs, tok_spec)

    mapped = jax.shard_map(
        body,
        mesh=plan.mesh,
        in_specs=in_specs,
        out_specs=(P(plan.client_axes), cache_specs),
        check_vma=False,
    )
    specs = {
        "param_meta": meta_tree,
        "param_specs": param_specs,
        "cache_meta": cache_meta,
        "token_spec": tok_spec,
    }
    return jax.jit(mapped), specs
