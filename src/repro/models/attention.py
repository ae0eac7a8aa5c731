"""GQA attention: manual tensor-parallel, chunked-causal train path, KV-cache
decode path, sliding-window support, and seq-sharded flash-decoding for
long-context decode.

Parameter layout convention (uniform across the framework): every sharded
parameter carries an explicit leading-ish ``tp`` dimension which is size 1
inside the manual shard_map (sliced by in_specs) and squeezed by ``L()``.
Duplicated slices (see common.AttnSharding) are materialized in the global
array — duplicates stay in sync because gradient sync sums over their
subgroup before the (deterministic) optimizer update.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import AttnSharding, ParallelCtx, apply_rope, dense_init, plan_attn_sharding

NEG_INF = -1e30


def squeeze_tp(p, axis: int):
    return jax.lax.squeeze(p, (axis,)) if p.shape[axis] == 1 else p


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """Static per-layer attention configuration."""

    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rotary_frac: float = 1.0  # 0.0: NoPE
    window: Optional[int] = None  # sliding-window size; None = full causal
    qkv_bias: bool = False
    q_chunk: int = 256  # query-block size for the chunked train/prefill path
    scale_override: Optional[float] = None

    @property
    def scale(self) -> float:
        return self.scale_override or 1.0 / math.sqrt(self.head_dim)


def plan(spec: AttentionSpec, tp: int) -> AttnSharding:
    return plan_attn_sharding(spec.num_heads, spec.num_kv_heads, tp)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(key, spec: AttentionSpec, tp: int, dtype=jnp.float32):
    """Global parameter arrays (with duplicated slices materialized)."""
    sh = plan(spec, tp)
    D, hd = spec.d_model, spec.head_dim
    kq, kkv, ko, kb = jax.random.split(key, 4)
    # Distinct content per tp_attn slice, tiled across duplicates.
    wq = dense_init(kq, (D, sh.tp_attn, sh.q_local * hd), in_axis=0, dtype=dtype)
    wq = jnp.repeat(wq, sh.dup_attn, axis=1)  # (D, tp, q_local*hd)
    wkv = dense_init(kkv, (D, sh.kv_shards, sh.kv_local * hd * 2), in_axis=0, dtype=dtype)
    wkv = jnp.repeat(wkv, sh.dup_kv * sh.dup_attn, axis=1)
    wo = dense_init(ko, (sh.tp_attn, sh.q_local * hd, D), in_axis=1, dtype=dtype)
    wo = jnp.repeat(wo, sh.dup_attn, axis=0)  # (tp, q_local*hd, D)
    p = {"wq": wq, "wkv": wkv, "wo": wo}
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((tp, sh.q_local * hd), dtype)
        p["bkv"] = jnp.zeros((tp, sh.kv_local * hd * 2), dtype)
    return p


def param_meta(spec: AttentionSpec, tp: int, dtype=jnp.float32):
    """Mirrors init_params: (global_shape, dtype, PartitionSpec, sync_group)."""
    from repro.models.meta import Meta  # local import to avoid cycle

    sh = plan(spec, tp)
    D, hd = spec.d_model, spec.head_dim
    m = {
        "wq": Meta((D, tp, sh.q_local * hd), dtype, P(None, "model", None), sh.dup_attn),
        "wkv": Meta((D, tp, sh.kv_local * hd * 2), dtype, P(None, "model", None), sh.kv_group),
        "wo": Meta((tp, sh.q_local * hd, D), dtype, P("model", None, None), sh.dup_attn),
    }
    if spec.qkv_bias:
        m["bq"] = Meta((tp, sh.q_local * hd), dtype, P("model", None), sh.dup_attn)
        m["bkv"] = Meta((tp, sh.kv_local * hd * 2), dtype, P("model", None), sh.kv_group)
    return m


# ---------------------------------------------------------------------------
# Forward (training / prefill): chunked causal attention
# ---------------------------------------------------------------------------


def _project_qkv(params, spec: AttentionSpec, sh: AttnSharding, x, positions):
    """x: (B, S, D) -> q (B,S,ql,hd), k,v (B,S,kvl,hd), rope applied
    (none where ``rotary_frac`` is 0: NoPE)."""
    hd = spec.head_dim
    wq = squeeze_tp(params["wq"], 1)
    wkv = squeeze_tp(params["wkv"], 1)
    q = jnp.einsum("bsd,dh->bsh", x, wq.astype(x.dtype))
    kv = jnp.einsum("bsd,dh->bsh", x, wkv.astype(x.dtype))
    if spec.qkv_bias:
        q = q + squeeze_tp(params["bq"], 0).astype(x.dtype)
        kv = kv + squeeze_tp(params["bkv"], 0).astype(x.dtype)
    B, S = x.shape[:2]
    q = q.reshape(B, S, sh.q_local, hd)
    kv = kv.reshape(B, S, sh.kv_local, 2, hd)
    k, v = kv[..., 0, :], kv[..., 1, :]
    if spec.rotary_frac > 0:
        q = apply_rope(q, positions, spec.rope_theta, spec.rotary_frac)
        k = apply_rope(k, positions, spec.rope_theta, spec.rotary_frac)
    return q, k, v


def _attend_chunk(q_blk, k, v, q_pos, k_pos, spec: AttentionSpec):
    """q_blk: (B, C, kvl, qpg, hd); k/v: (B, Sk, kvl, hd). Causal + window."""
    scores = jnp.einsum("bckgh,bskh->bkgcs", q_blk, k).astype(jnp.float32)
    scores = scores * spec.scale
    # k_pos >= 0 masks the windowed path's front padding: a query at
    # q_pos < window otherwise ATTENDS the zero-vector padding keys
    # (score 0 is not -inf — it survives the softmax and dilutes the
    # distribution), which made the chunked forward disagree with the
    # un-padded decode path for every position before the window fills.
    causal = (q_pos[:, None] >= k_pos[None, :]) & (k_pos[None, :] >= 0)
    if spec.window is not None:
        causal &= k_pos[None, :] > q_pos[:, None] - spec.window
    scores = jnp.where(causal[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
    return jnp.einsum("bkgcs,bskh->bckgh", w, v)


def forward(params, spec: AttentionSpec, ctx: ParallelCtx, x, positions):
    """Training/prefill attention. x: (B, S, D) replicated over model axis.

    Queries are processed in blocks of q_chunk; for sliding-window layers
    only the [blk_start - window, blk_end) key slice is read, making compute
    O(S * window) rather than O(S^2).
    """
    sh = plan(spec, ctx.tp)
    B, S, D = x.shape
    q, k, v = _project_qkv(params, spec, sh, x, positions)
    qpg = sh.q_local // sh.kv_local  # q heads per local kv head
    q = q.reshape(B, S, sh.kv_local, qpg, spec.head_dim)

    C = min(spec.q_chunk, S)
    if S % C != 0:
        C = S  # irregular (small/test) lengths: single chunk
    n_chunks = S // C

    if spec.window is not None and spec.window < S:
        W = ((spec.window + C - 1) // C) * C  # pad window to chunk multiple
        k_pad = jnp.pad(k, ((0, 0), (W, 0), (0, 0), (0, 0)))
        v_pad = jnp.pad(v, ((0, 0), (W, 0), (0, 0), (0, 0)))

        def blk(i):
            c0 = i * C
            q_blk = jax.lax.dynamic_slice_in_dim(q, c0, C, axis=1)
            k_blk = jax.lax.dynamic_slice_in_dim(k_pad, c0, W + C, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v_pad, c0, W + C, axis=1)
            q_pos = c0 + jnp.arange(C)
            k_pos = c0 - W + jnp.arange(W + C)  # negatives are padding -> masked
            return _attend_chunk(q_blk, k_blk, v_blk, q_pos, k_pos, spec)
    else:

        def blk(i):
            c0 = i * C
            q_blk = jax.lax.dynamic_slice_in_dim(q, c0, C, axis=1)
            q_pos = c0 + jnp.arange(C)
            k_pos = jnp.arange(S)
            return _attend_chunk(q_blk, k, v, q_pos, k_pos, spec)

    # Chunk-level remat: without it the backward scan saves every chunk's
    # scores/softmax residuals ((B,h,C,S) f32 per chunk — gigabytes/layer);
    # with it only the chunk outputs survive the forward.
    blk = jax.checkpoint(blk)
    out = jax.lax.map(blk, jnp.arange(n_chunks))  # (n_chunks, B, C, kvl, qpg, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, sh.q_local * spec.head_dim)

    wo = squeeze_tp(params["wo"], 0)
    y = jnp.einsum("bsh,hd->bsd", out, wo.astype(out.dtype))
    y = ctx.sp_scatter(y)
    if sh.dup_attn > 1:
        y = y / sh.dup_attn
    return y


# ---------------------------------------------------------------------------
# Decode (serve): single new token against a KV cache
# ---------------------------------------------------------------------------


def init_cache_shape(spec: AttentionSpec, tp: int, batch: int, max_len: int):
    sh = plan(spec, tp)
    return {
        "k": (batch, tp, sh.kv_local, max_len, spec.head_dim),
        "v": (batch, tp, sh.kv_local, max_len, spec.head_dim),
    }


# --- int8 KV-cache quantization (§Perf: halves decode cache traffic) -------


def quant_kv(x):
    """(…, hd) -> (int8 codes, per-vector bf16 scale). Symmetric per-token
    quantization — the same unbiased-rounding-to-a-grid idea as the paper's
    mechanism, applied to the KV cache instead of gradients."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequant_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def _cache_read(cache, prefix, dtype):
    """Read k or v from a cache dict, dequantizing if stored int8."""
    buf = squeeze_tp(cache[prefix], 1)
    if buf.dtype == jnp.int8:
        scale = squeeze_tp(cache[prefix + "_scale"], 1)
        return dequant_kv(buf, scale, dtype)
    return buf


def _cache_write(cache, prefix, buf_local, new, idx, *, masked_write=None):
    """Write one token (already transposed to (B, kvl, 1, hd)) at idx,
    quantizing if the cache is int8. Returns updated cache entries dict."""
    out = {}
    if cache[prefix].dtype == jnp.int8:
        q, s = quant_kv(new)
        sc = squeeze_tp(cache[prefix + "_scale"], 1)
        if masked_write is not None:
            old_q = jax.lax.dynamic_slice_in_dim(buf_local, idx, 1, axis=2)
            old_s = jax.lax.dynamic_slice_in_dim(sc, idx, 1, axis=2)
            q = jnp.where(masked_write, q, old_q)
            s = jnp.where(masked_write, s, old_s)
        buf_local = jax.lax.dynamic_update_slice_in_dim(buf_local, q, idx, axis=2)
        sc = jax.lax.dynamic_update_slice_in_dim(sc, s, idx, axis=2)
        out[prefix + "_scale"] = sc[:, None]
    else:
        new = new.astype(buf_local.dtype)
        if masked_write is not None:
            old = jax.lax.dynamic_slice_in_dim(buf_local, idx, 1, axis=2)
            new = jnp.where(masked_write, new, old)
        buf_local = jax.lax.dynamic_update_slice_in_dim(buf_local, new, idx, axis=2)
    out[prefix] = buf_local[:, None]
    return out


def decode(params, spec: AttentionSpec, ctx: ParallelCtx, x, cache, pos,
           *, seq_sharded: bool = False):
    """One decode step. x: (B, 1, D); cache entries (B, 1(tp), kvl, S, hd)
    locally. pos: scalar int32 — number of tokens already in the cache.

    seq_sharded: the cache's S dim is sharded over ctx.seq_axis
    (flash-decoding): each shard attends over its local keys and partial
    softmaxes are combined with a max/psum log-sum-exp reduction.
    Returns (y (B,1,D), new_cache).
    """
    sh = plan(spec, ctx.tp)
    B = x.shape[0]
    hd = spec.head_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, spec, sh, x, positions)
    q = q.reshape(B, sh.kv_local, sh.q_local // sh.kv_local, hd)

    k_store = squeeze_tp(cache["k"], 1)  # raw storage (bf16 or int8 codes)
    v_store = squeeze_tp(cache["v"], 1)
    S_local = k_store.shape[2]

    new_entries = {}
    if seq_sharded:
        # Writer shard = the one whose slice contains `pos`; other shards
        # re-write the value they already hold (a masked no-op update).
        shard_id = ctx.seq_index()
        local_pos = pos - shard_id * S_local
        write = (local_pos >= 0) & (local_pos < S_local)
        idx = jnp.clip(local_pos, 0, S_local - 1)
        new_entries.update(_cache_write(
            cache, "k", k_store, k_new.transpose(0, 2, 1, 3), idx,
            masked_write=write))
        new_entries.update(_cache_write(
            cache, "v", v_store, v_new.transpose(0, 2, 1, 3), idx,
            masked_write=write))
        k_pos = shard_id * S_local + jnp.arange(S_local)
    else:
        new_entries.update(_cache_write(
            cache, "k", k_store, k_new.transpose(0, 2, 1, 3), pos))
        new_entries.update(_cache_write(
            cache, "v", v_store, v_new.transpose(0, 2, 1, 3), pos))
        k_pos = jnp.arange(S_local)
    new_cache = {**cache, **new_entries}
    k_cache = _cache_read(new_cache, "k", q.dtype)  # (B, kvl, S_local, hd)
    v_cache = _cache_read(new_cache, "v", q.dtype)

    scores = jnp.einsum("bkgh,bksh->bkgs", q, k_cache).astype(jnp.float32)
    scores = scores * spec.scale
    valid = k_pos <= pos
    if spec.window is not None:
        valid &= k_pos > pos - spec.window
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)

    if seq_sharded and ctx.seq_axis is not None:
        # Flash-decoding combine: local max -> global max, exp-sum psum.
        m_local = jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
        m = jax.lax.pmax(m_local, ctx.seq_axis)
        e = jnp.exp(scores - m)
        num = jnp.einsum("bkgs,bksh->bkgh", e.astype(v_cache.dtype), v_cache)
        den = jnp.sum(e, axis=-1)[..., None].astype(v_cache.dtype)
        num = jax.lax.psum(num, ctx.seq_axis)
        den = jax.lax.psum(den, ctx.seq_axis)
        attn = num / den
    else:
        w = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
        attn = jnp.einsum("bkgs,bksh->bkgh", w, v_cache)

    attn = attn.reshape(B, 1, sh.q_local * hd)
    wo = squeeze_tp(params["wo"], 0)
    y = jnp.einsum("bsh,hd->bsd", attn, wo.astype(attn.dtype))
    y = ctx.psum_model(y)
    if sh.dup_attn > 1:
        y = y / sh.dup_attn
    return y, new_cache


def prefill_kv(params, spec: AttentionSpec, ctx: ParallelCtx, x, positions, max_len: int):
    """Compute k/v for a whole prompt and lay them out as a decode cache.
    Returns (attn_out, cache) — attn_out is the standard causal forward."""
    sh = plan(spec, ctx.tp)
    B, S, _ = x.shape
    _, k, v = _project_qkv(params, spec, sh, x, positions)
    pad = max_len - S
    k_c = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
    v_c = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
    y = forward(params, spec, ctx, x, positions)
    return y, {"k": k_c[:, None], "v": v_c[:, None]}
