"""Model assembly: embedding -> blocks (attn/ssm/moe/shared) -> vocab-parallel
LM head. All functions run inside the full-manual shard_map (or locally with
ctx.tp == 1 — identical code path, collectives are no-ops).

Parameter pytree (mirrored by param_meta/init_params):
  embed:      (tp, V_l, D)  vocab-parallel table
  layers[i]:  {"norm1", "attn"/"ssm", ["norm2", "mlp"/"moe"]} (an ssm layer
              carries norm2 and an MLP where cfg.ssm_mlp)
  shared:     one attention+MLP block reused by all 'shared_attn' layers
  final_norm: (D,)
  lm_head:    (D, tp, V_l) column-parallel; absent where cfg.tie_embeddings
              (the head is then the embedding table transposed)

Each sublayer opens a device scope (``jax.named_scope``), so a device trace
splits a client's gradient by layer kind: ``fed.layer.ssm`` and
``fed.layer.attn`` (norm1, the mixer, its residual), ``fed.layer.mlp`` or
``fed.layer.moe`` (norm2, the MLP or experts, its residual) and
``fed.layer.head`` (the embedding, the final norm and the LM head's loss).
Under ``jax.grad`` a scope's name stays bare (what ``bench/scopes.py`` reads)
only where it opens inside a recomputed region: every layer when the loss
runs with ``remat``, and the LM head's loss chunks (always recomputed);
elsewhere the forward ops read ``jvp(fed.layer.*)`` and a split counts them
under the enclosing ``fed.client_grad``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, LayerSpec, ModelConfig
from repro.models import attention, mlp, moe, ssm
from repro.models.attention import squeeze_tp
from repro.models.common import ParallelCtx, dense_init, rms_norm
from repro.models.meta import Meta


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _layer_init(key, cfg: ModelConfig, layer: LayerSpec, tp: int, dtype):
    D = cfg.d_model
    if layer.kind == "shared_attn":
        return {}  # params live in the shared block
    p = {"norm1": jnp.zeros((D,), dtype)}
    if layer.kind == "ssm":
        p["ssm"] = ssm.init_params(key, cfg.ssm, tp, dtype)
        if not cfg.ssm_mlp:
            return p
        k2 = jax.random.fold_in(key, 1)
    else:
        k1, k2 = jax.random.split(key)
        p["attn"] = attention.init_params(k1, cfg.attn_spec(layer), tp, dtype)
    p["norm2"] = jnp.zeros((D,), dtype)
    if cfg.moe is not None:
        p["moe"] = moe.init_params(k2, cfg.moe, tp, dtype)
    elif cfg.mlp_kind is not None:
        p["mlp"] = mlp.init_params(k2, cfg.mlp_kind, D, cfg.d_ff, tp, dtype)
    return p


def _layer_meta(cfg: ModelConfig, layer: LayerSpec, tp: int, dtype):
    D = cfg.d_model
    if layer.kind == "shared_attn":
        return {}
    m = {"norm1": Meta((D,), dtype, P(None), tp)}
    if layer.kind == "ssm":
        m["ssm"] = ssm.param_meta(cfg.ssm, tp, dtype)
        if not cfg.ssm_mlp:
            return m
    else:
        m["attn"] = attention.param_meta(cfg.attn_spec(layer), tp, dtype)
    m["norm2"] = Meta((D,), dtype, P(None), tp)
    if cfg.moe is not None:
        m["moe"] = moe.param_meta(cfg.moe, tp, dtype)
    elif cfg.mlp_kind is not None:
        m["mlp"] = mlp.param_meta(cfg.mlp_kind, D, cfg.d_ff, tp, dtype)
    return m


def _shared_layerspec(cfg: ModelConfig) -> LayerSpec:
    for l in cfg.layers:
        if l.kind == "shared_attn":
            return l
    raise ValueError("no shared_attn layer in config")


def init_params(key, cfg: ModelConfig, tp: int = 1, dtype=jnp.float32):
    D = cfg.d_model
    V = cfg.padded_vocab(tp)
    keys = jax.random.split(key, cfg.num_layers + 4)
    params = {
        "embed": dense_init(keys[0], (tp, V // tp, D), in_axis=2, dtype=dtype),
        "layers": tuple(
            _layer_init(keys[i + 1], cfg, layer, tp, dtype)
            for i, layer in enumerate(cfg.layers)
        ),
        "final_norm": jnp.zeros((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[-1], (D, tp, V // tp), in_axis=0,
                                       dtype=dtype)
    if cfg.shared_attn:
        ks1, ks2 = jax.random.split(keys[-2])
        spec = cfg.attn_spec(_shared_layerspec(cfg))
        params["shared"] = {
            "norm1": jnp.zeros((D,), dtype),
            "attn": attention.init_params(ks1, spec, tp, dtype),
            "norm2": jnp.zeros((D,), dtype),
            "mlp": mlp.init_params(ks2, cfg.mlp_kind, D, cfg.shared_d_ff, tp, dtype),
        }
    return params


def param_meta(cfg: ModelConfig, tp: int = 1, dtype=jnp.float32):
    D = cfg.d_model
    V = cfg.padded_vocab(tp)
    m = {
        "embed": Meta((tp, V // tp, D), dtype, P("model", None, None), 1),
        "layers": tuple(
            _layer_meta(cfg, layer, tp, dtype) for layer in cfg.layers
        ),
        "final_norm": Meta((D,), dtype, P(None), tp),
    }
    if not cfg.tie_embeddings:
        m["lm_head"] = Meta((D, tp, V // tp), dtype, P(None, "model", None), 1)
    if cfg.shared_attn:
        spec = cfg.attn_spec(_shared_layerspec(cfg))
        m["shared"] = {
            "norm1": Meta((D,), dtype, P(None), tp),
            "attn": attention.param_meta(spec, tp, dtype),
            "norm2": Meta((D,), dtype, P(None), tp),
            "mlp": mlp.param_meta(cfg.mlp_kind, D, cfg.shared_d_ff, tp, dtype),
        }
    return m


# ---------------------------------------------------------------------------
# Embedding / head (vocab-parallel)
# ---------------------------------------------------------------------------


def embed(params, cfg: ModelConfig, ctx: ParallelCtx, tokens):
    """tokens (B, S) -> (B, S, D). Local table rows + psum over model, times
    ``cfg.embedding_multiplier``."""
    table = squeeze_tp(params["embed"], 0)  # (V_l, D)
    v_l = table.shape[0]
    lo = ctx.model_index() * v_l
    ids = tokens - lo
    valid = (ids >= 0) & (ids < v_l)
    emb = jnp.take(table, jnp.clip(ids, 0, v_l - 1), axis=0)
    emb = jnp.where(valid[..., None], emb, 0)
    if cfg.embedding_multiplier != 1.0:
        emb = emb * cfg.embedding_multiplier
    return ctx.psum_model(emb)


def _head(params):
    """The local LM head (D, V_l): its own matrix, or the vocab-parallel
    embedding table transposed where the config ties them (init_params then
    makes no ``lm_head``)."""
    if "lm_head" in params:
        return squeeze_tp(params["lm_head"], 1)
    return squeeze_tp(params["embed"], 0).T


def lm_head_loss(params, cfg: ModelConfig, ctx: ParallelCtx, h, labels,
                 *, seq_chunk: int = 512):
    """Vocab-parallel cross entropy. h: (B, S, D); labels: (B, S) int32,
    positions with label < 0 are masked out. Returns (mean_loss, n_tokens).

    The full-vocab logits tensor is never materialized: each shard computes
    its (B, S_chunk, V_l) slice per SEQUENCE CHUNK (rematted — peak logits
    memory is (B, seq_chunk, V/tp) f32 rather than the full sequence), and
    the log-sum-exp / target-logit terms combine with pmax/psum over the
    model axis.
    """
    head = _head(params)  # (D, V_l)
    v_l = head.shape[1]
    lo = ctx.model_index() * v_l
    B, S, _ = h.shape
    cs = min(seq_chunk, S)
    n_chunks = S // cs if S % cs == 0 else 1
    if S % cs != 0:
        cs = S

    def chunk_loss(args):
        h_c, labels_c = args  # (B, cs, D), (B, cs)
        # the scope opens inside the recomputed chunk, so that the head's
        # backward ops carry it too (bench/scopes.py reads the bare name)
        with jax.named_scope("fed.layer.head"):
            logits = jnp.einsum("bsd,dv->bsv", h_c, head.astype(h_c.dtype)).astype(jnp.float32)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
            # stop_gradient BEFORE the pmax: pmax has no differentiation
            # rule, and the max is only a stabilization shift anyway.
            mx = ctx.pmax_model(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
            )
            sumexp = jnp.sum(jnp.exp(logits - mx), axis=-1)
            lse = jnp.log(ctx.psum_model(sumexp)) + mx[..., 0]
            ids = labels_c - lo
            valid = (ids >= 0) & (ids < v_l)
            tgt_local = jnp.take_along_axis(
                logits, jnp.clip(ids, 0, v_l - 1)[..., None], axis=-1
            )[..., 0]
            tgt = ctx.psum_model(jnp.where(valid, tgt_local, 0.0))
            mask = (labels_c >= 0).astype(jnp.float32)
            return jnp.sum((lse - tgt) * mask)

    h_c = h.reshape(B, n_chunks, cs, -1).transpose(1, 0, 2, 3)
    l_c = labels.reshape(B, n_chunks, cs).transpose(1, 0, 2)
    per_chunk = jax.lax.map(jax.checkpoint(chunk_loss), (h_c, l_c))
    mask = (labels >= 0).astype(jnp.float32)
    n_tok = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(per_chunk) / n_tok
    return loss, n_tok


def lm_head_argmax(params, ctx: ParallelCtx, h):
    """Greedy next-token over the vocab-parallel head. h: (B, D) -> (B,).
    (``logits_scaling`` divides every logit alike and moves no argmax.)"""
    head = _head(params)
    v_l = head.shape[1]
    logits = jnp.einsum("bd,dv->bv", h, head.astype(h.dtype)).astype(jnp.float32)
    local_best = jnp.max(logits, axis=-1)
    local_arg = jnp.argmax(logits, axis=-1) + ctx.model_index() * v_l
    best = ctx.pmax_model(local_best)
    # break ties toward the smallest global id
    cand = jnp.where(local_best >= best, local_arg, jnp.iinfo(jnp.int32).max)
    if ctx.model_axis is not None and ctx.tp > 1:
        cand = jax.lax.pmin(cand, ctx.model_axis)
    return cand.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _residual(cfg: ModelConfig, x, y):
    """x + y, the sublayer's output scaled by ``residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _norm(cfg: ModelConfig, x, w):
    return rms_norm(x, w, cfg.norm_eps)


def _mlp_sublayer(p, cfg: ModelConfig, layer: LayerSpec, ctx: ParallelCtx, x,
                  *, decode: bool = False):
    """norm2, the layer's MLP (or experts) and its residual: (x, aux or None).
    The decode step (one token) takes no sequence-parallel gather."""
    h = _norm(cfg, x, p["norm2"])
    if not decode:
        h = ctx.sp_gather(h)
    if layer.kind != "shared_attn" and cfg.moe is not None:
        with jax.named_scope("fed.layer.moe"):
            y, aux = moe.forward(p["moe"], cfg.moe, ctx, h, decode=decode)
            return _residual(cfg, x, y), aux
    with jax.named_scope("fed.layer.mlp"):
        return _residual(cfg, x, mlp.forward(p["mlp"], cfg.mlp_kind, ctx, h)), None


def _has_mlp(cfg: ModelConfig, layer: LayerSpec) -> bool:
    return layer.kind != "ssm" or cfg.ssm_mlp


def _block_apply(layer_params, shared_params, cfg: ModelConfig, layer: LayerSpec,
                 ctx: ParallelCtx, x, positions):
    """With sequence parallelism the residual x is (B, S/tp, D): norms act
    per-token on the shard, sublayers all-gather on entry (sp_gather) and
    reduce-scatter on exit (sp_scatter, inside each sublayer)."""
    p = shared_params if layer.kind == "shared_attn" else layer_params
    if layer.kind == "ssm":
        with jax.named_scope("fed.layer.ssm"):
            h = ctx.sp_gather(_norm(cfg, x, p["norm1"]))
            x = _residual(cfg, x, ssm.forward(p["ssm"], cfg.ssm, ctx, h,
                                              eps=cfg.norm_eps))
    else:
        with jax.named_scope("fed.layer.attn"):
            h = ctx.sp_gather(_norm(cfg, x, p["norm1"]))
            x = _residual(cfg, x, attention.forward(
                p["attn"], cfg.attn_spec(layer), ctx, h, positions))
    if not _has_mlp(cfg, layer):
        return x, None
    return _mlp_sublayer(p, cfg, layer, ctx, x)


def forward_hidden(params, cfg: ModelConfig, ctx: ParallelCtx, tokens,
                   prefix_embeds=None, *, remat: bool = False,
                   compute_dtype=jnp.float32):
    """tokens (B, S_t); prefix_embeds (B, P, D) or None -> hidden (B, S, D)
    with S = P + S_t. Also returns summed MoE aux dict."""
    with jax.named_scope("fed.layer.head"):
        x = embed(params, cfg, ctx, tokens).astype(compute_dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(compute_dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    # Enter sequence-parallel form: residual stream (B, S/tp, D). The slice
    # is collective-free; its transpose (zero-pad) composes with the embed
    # psum to recover full cotangents.
    x = ctx.sp_slice(x)

    aux_losses = []
    for layer_params, layer in zip(params["layers"], cfg.layers):
        fn = functools.partial(_block_apply, cfg=cfg, layer=layer, ctx=ctx)
        if remat:
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable
            )
        x, aux = fn(layer_params, params.get("shared"), x=x, positions=positions)
        if aux is not None:
            aux_losses.append(aux["moe_aux_loss"])
    with jax.named_scope("fed.layer.head"):
        x = ctx.sp_gather(_norm(cfg, x, params["final_norm"]))
    moe_aux = sum(aux_losses) if aux_losses else jnp.float32(0.0)
    return x, {"moe_aux_loss": moe_aux}


def loss_fn(params, cfg: ModelConfig, ctx: ParallelCtx, batch, *,
            remat: bool = True, compute_dtype=jnp.bfloat16):
    """Next-token CE (+ MoE aux). batch: {"tokens", "labels"[, "prefix_embeds"]}.
    labels align with the FULL sequence (prefix positions must carry -1)."""
    h, aux = forward_hidden(
        params, cfg, ctx, batch["tokens"], batch.get("prefix_embeds"),
        remat=remat, compute_dtype=compute_dtype,
    )
    loss, n_tok = lm_head_loss(params, cfg, ctx, h, batch["labels"])
    total = loss + aux["moe_aux_loss"]
    return total, {"ce_loss": loss, "n_tokens": n_tok, **aux}


# ---------------------------------------------------------------------------
# Serving: cache construction, prefill, decode
# ---------------------------------------------------------------------------


def cache_meta(cfg: ModelConfig, tp: int, shape: InputShape,
               client_axes: tuple, *, dtype=jnp.bfloat16,
               kv_quant: bool = False):
    """Meta tree for the KV/SSM caches of one serving config.

    decode_32k: batch sharded over client axes, full seq per shard.
    long_500k (global_batch == 1): attention caches sharded over the client
    axes on the SEQ dim (flash-decoding); SSM states replicated.
    kv_quant (§Perf): store K/V as int8 codes + per-token bf16 scales
    (~2x less cache traffic and capacity).
    """
    B = shape.global_batch
    seq_sharded = B == 1
    batch_spec = None if seq_sharded else client_axes
    seq_spec = client_axes if seq_sharded else None
    caches = []
    for layer in cfg.layers:
        if layer.kind == "ssm":
            s = ssm.init_state_shape(cfg.ssm, tp, B)
            caches.append({
                "h": Meta(s["h"], jnp.float32, P(batch_spec, "model", None, None, None), 1),
                "conv_x": Meta(s["conv_x"], dtype, P(batch_spec, "model", None, None), 1),
                "conv_bc": Meta(s["conv_bc"], dtype, P(batch_spec, None, None), 1),
            })
        else:
            spec = cfg.attn_spec(layer)
            # SWA layers only ever read the last `window` keys: cache only
            # that many (ring buffer) — this is what makes long_500k viable.
            S_c = shape.seq_len if layer.window is None else min(shape.seq_len, layer.window)
            layer_seq_spec = seq_spec if (layer.window is None and seq_sharded) else None
            if seq_sharded and layer.window is not None:
                bs = None  # batch 1, window cache replicated
            else:
                bs = batch_spec
            c = attention.init_cache_shape(spec, tp, B, S_c)
            pspec = P(bs, "model", None, layer_seq_spec, None)
            if kv_quant:
                scale_shape = c["k"][:-1] + (1,)
                caches.append({
                    "k": Meta(c["k"], jnp.int8, pspec, 1),
                    "k_scale": Meta(scale_shape, jnp.bfloat16, pspec, 1),
                    "v": Meta(c["v"], jnp.int8, pspec, 1),
                    "v_scale": Meta(scale_shape, jnp.bfloat16, pspec, 1),
                })
            else:
                caches.append({
                    "k": Meta(c["k"], dtype, pspec, 1),
                    "v": Meta(c["v"], dtype, pspec, 1),
                })
    return tuple(caches)


def decode_step(params, caches, cfg: ModelConfig, ctx: ParallelCtx, tokens, pos,
                *, seq_sharded: bool = False, compute_dtype=jnp.bfloat16):
    """One decode step. tokens (B, 1); pos scalar int32 (tokens in cache).
    Returns (next_token (B,), new_caches)."""
    x = embed(params, cfg, ctx, tokens).astype(compute_dtype)
    new_caches = []
    for layer_params, layer, cache in zip(params["layers"], cfg.layers, caches):
        p = params.get("shared") if layer.kind == "shared_attn" else layer_params
        h = _norm(cfg, x, p["norm1"])
        if layer.kind == "ssm":
            y, new_c = ssm.decode(p["ssm"], cfg.ssm, ctx, h, cache,
                                  eps=cfg.norm_eps)
        else:
            spec = cfg.attn_spec(layer)
            S_c = cache["k"].shape[3]
            if layer.window is not None and S_c <= layer.window:
                # ring-buffer window cache: write at pos % window
                y, new_c = _decode_ring(p["attn"], spec, ctx, h, cache, pos, S_c)
            else:
                y, new_c = attention.decode(
                    p["attn"], spec, ctx, h, cache, pos,
                    seq_sharded=seq_sharded and layer.window is None,
                )
        x = _residual(cfg, x, y)
        if _has_mlp(cfg, layer):
            x, _ = _mlp_sublayer(p, cfg, layer, ctx, x, decode=True)
        new_caches.append(new_c)
    x = _norm(cfg, x, params["final_norm"])
    nxt = lm_head_argmax(params, ctx, x[:, 0])
    return nxt, tuple(new_caches)


def _decode_ring(attn_params, spec, ctx: ParallelCtx, x, cache, pos, window):
    """Sliding-window decode against a ring-buffer cache of size `window`.
    Key absolute positions are reconstructed from the write pointer."""
    from repro.models.attention import plan, _project_qkv

    sh = plan(spec, ctx.tp)
    B = x.shape[0]
    hd = spec.head_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(attn_params, spec, sh, x, positions)
    q = q.reshape(B, sh.kv_local, sh.q_local // sh.kv_local, hd)

    from repro.models.attention import _cache_read, _cache_write

    slot = pos % window
    new_cache = dict(cache)
    new_cache.update(_cache_write(
        cache, "k", squeeze_tp(cache["k"], 1),
        k_new.transpose(0, 2, 1, 3), slot))
    new_cache.update(_cache_write(
        cache, "v", squeeze_tp(cache["v"], 1),
        v_new.transpose(0, 2, 1, 3), slot))
    k_cache = _cache_read(new_cache, "k", q.dtype)
    v_cache = _cache_read(new_cache, "v", q.dtype)

    # absolute position of ring slot s: the most recent write to that slot
    slots = jnp.arange(window)
    abs_pos = jnp.where(slots <= slot, pos - slot + slots, pos - slot - window + slots)
    valid = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - window)

    scores = jnp.einsum("bkgh,bksh->bkgs", q, k_cache).astype(jnp.float32) * spec.scale
    scores = jnp.where(valid[None, None, None], scores, attention.NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    attn_out = jnp.einsum("bkgs,bksh->bkgh", w, v_cache).reshape(B, 1, sh.q_local * hd)
    wo = squeeze_tp(attn_params["wo"], 0)
    y = jnp.einsum("bsh,hd->bsd", attn_out, wo.astype(attn_out.dtype))
    y = ctx.psum_model(y)
    if sh.dup_attn > 1:
        y = y / sh.dup_attn
    return y, new_cache


def prefill(params, cfg: ModelConfig, ctx: ParallelCtx, tokens, shape: InputShape,
            prefix_embeds=None, *, compute_dtype=jnp.bfloat16):
    """Prefill: run the prompt through the model, building decode caches.
    Returns (next_token (B,), caches). Cache layouts match cache_meta for the
    same InputShape (batch-sharded; prefill is never seq-sharded here)."""
    x = embed(params, cfg, ctx, tokens).astype(compute_dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(compute_dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    # Sequence-parallel prefill (§Perf): residual sharded (B, S/tp, D);
    # sublayers gather on entry (k/v caches are built from the gathered h).
    x = ctx.sp_slice(x)
    caches = []
    for layer_params, layer in zip(params["layers"], cfg.layers):
        p = params.get("shared") if layer.kind == "shared_attn" else layer_params
        h = ctx.sp_gather(_norm(cfg, x, p["norm1"]))
        if layer.kind == "ssm":
            y, state = ssm.forward(
                p["ssm"], cfg.ssm, ctx, h, eps=cfg.norm_eps, return_state=True
            )
            cache = jax.tree_util.tree_map(
                lambda t: t.astype(jnp.float32) if t.ndim == 5 else t.astype(compute_dtype),
                state,
            )
        else:
            spec = cfg.attn_spec(layer)
            S_c = shape.seq_len if layer.window is None else min(shape.seq_len, layer.window)
            y, cache = attention.prefill_kv(
                p["attn"], spec, ctx, h, positions, max_len=max(S_c, S)
            )
            if layer.window is not None and S_c < max(S_c, S):
                # Re-lay the last S_c keys into ring order (slot = pos % S_c),
                # matching the _decode_ring invariant.
                idx = [0] * S_c
                for pos_abs in range(S - S_c, S):
                    idx[pos_abs % S_c] = pos_abs
                idx = jnp.asarray(idx, jnp.int32)
                cache = {
                    "k": jnp.take(cache["k"], idx, axis=3),
                    "v": jnp.take(cache["v"], idx, axis=3),
                }
        x = _residual(cfg, x, y)
        if _has_mlp(cfg, layer):
            x, _ = _mlp_sublayer(p, cfg, layer, ctx, x)
        caches.append(cache)
    x = ctx.sp_gather(_norm(cfg, x, params["final_norm"]))
    nxt = lm_head_argmax(params, ctx, x[:, -1])
    return nxt, tuple(caches)
