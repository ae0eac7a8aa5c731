"""granite-4.0-h-micro at its published widths, layers 2-5 of 40 and one
eighth of the vocabulary: what the program runs, and a plain reference of
the same network.

The published model (config.json; transformers' ``granitemoehybrid``):

* ``x = 12 * embed(ids)``; the head is the embedding, ``logits = h E^T / 8``;
* each layer ``x += 0.22 * mixer(RMSNorm(x))``, then
  ``x += 0.22 * W_o(silu(x' W_g) * x' W_u)`` with ``x' = RMSNorm(x)``;
  every RMSNorm at eps 1e-5;
* the Mamba2 mixer: 64 heads of 64, d_state 128, one B/C group, a
  depthwise convolution of width 4 with a bias, chunk 256, a gated RMSNorm
  ``norm(y * silu(z))`` over all 4,096 channels;
* the attention mixer: 32 query heads over 8 KV heads of 64, no bias, no
  position embedding (NoPE), causal softmax at scale 0.015625.

Departures from it, which the reference follows, since it is compared with
the program as run:

* depth: ``num_hidden_layers`` of ``layer_types`` from ``first_layer`` on:
  layers 2-5, three Mamba2 layers and then the first attention layer (the
  rest stand for further pipeline stages). Four, not six: the benchmark's
  comparison (``bench/correct.py``) holds about twelve model-sized vectors
  on the chip at once, which at six layers (467M coordinates) ran out of
  the v5e's 16 GiB; four layers are 315M;
* vocabulary: the first 12,544 of 100,352 ids, one chip's slice of a
  vocabulary-parallel deployment over 8; the softmax runs over the slice
  (the program's psum over the other seven slices is a no-op on one chip);
* every RMSNorm scales by ``1 + w`` (the program's parametrization; the
  published ``w`` is this ``1 + w``);
* the MLP's ``input_linear`` (2,048 -> 2 x 8,192, gate first) is held as
  its two halves, ``w_gate`` and ``w_up``.

The configuration states float32 parameters and activations at JAX's
default matmul precision. The reference writes each contraction as the
program does (the chunked SSD of ``mamba2_370m.py``, loaded from there,
scores before the scale, grouped query heads), so that at that precision
both round the same operands to the MXU's input width. It recomputes each
layer in the backward pass (``jax.checkpoint``), which changes no value,
so that one client's gradient fits beside the cohort's state on one chip.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

import plugins

SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
REDUCED = {
    "num_hidden_layers": "40 -> 4: layers 2-5, three Mamba2 and the first "
                         "attention layer, each with its MLP",
    "vocab_size": "100,352 -> 12,544: one chip's slice of a "
                  "vocabulary-parallel deployment over 8",
}
# widths small enough for a test run on the CPU (bench/rehearse.py)
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "shared_intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_chunk_size": 8, "vocab_size": 500}

_SSD = plugins.load_path(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "mamba2_370m.py"))._ssd


def _sizes(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hm, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    L, first = cfg["num_hidden_layers"], cfg.get("first_layer", 0)
    if Hm * P != cfg["mamba_expand"] * D:
        raise ValueError("mamba_n_heads x mamba_d_head must be expand x hidden")
    return {
        "D": D, "L": L, "F": cfg["shared_intermediate_size"],
        "V": -(-cfg["vocab_size"] // 128) * 128,  # the program's padding
        "Hq": H, "KV": cfg["num_key_value_heads"], "hd": D // H,
        "di": Hm * P, "Hm": Hm, "P": P, "N": cfg["mamba_d_state"],
        "W": cfg["mamba_d_conv"], "Q": cfg["mamba_chunk_size"],
        "kinds": tuple("attn" if t == "attention" else "ssm"
                       for t in cfg["layer_types"][first:first + L]),
        "eps": cfg["rms_norm_eps"], "emb": cfg["embedding_multiplier"],
        "res": cfg["residual_multiplier"], "logit": cfg["logits_scaling"],
        "scale": cfg["attention_multiplier"],
    }


def model_config(cfg):
    """The program's ModelConfig for these sizes (its published 40-layer
    config, cut to the stated layers and ``vocab_size``)."""
    from repro.configs.granite_4_0_h_micro import config
    from repro.configs.base import LayerSpec
    from repro.models.ssm import SSMSpec

    fixed = {"mamba_n_groups": 1, "position_embedding_type": "nope",
             "hidden_act": "silu", "num_local_experts": 0,
             "mamba_proj_bias": False, "attention_bias": False,
             "tie_word_embeddings": True, "normalization_function": "rmsnorm"}
    for k, v in fixed.items():
        if cfg[k] != v:
            raise ValueError(f"the program runs {k}={v!r}, not {cfg[k]!r}")
    s = _sizes(cfg)
    return dataclasses.replace(
        config(), num_layers=s["L"],
        layers=tuple(LayerSpec(k) for k in s["kinds"]), d_model=s["D"],
        num_heads=s["Hq"], num_kv_heads=s["KV"], head_dim=s["hd"],
        d_ff=s["F"], vocab_size=cfg["vocab_size"], norm_eps=s["eps"],
        embedding_multiplier=float(s["emb"]),
        residual_multiplier=float(s["res"]),
        logits_scaling=float(s["logit"]), attn_scale=float(s["scale"]),
        ssm=SSMSpec(d_model=s["D"], state_dim=s["N"], head_dim=s["P"],
                    expand=cfg["mamba_expand"], conv_width=s["W"],
                    chunk=s["Q"], conv_bias=cfg["mamba_conv_bias"]))


def init_weights(cfg, key):
    """Seeded weights in the program's parameter layout (one model shard:
    the axis of size 1 is the program's tensor-parallel axis; no
    ``lm_head``, the head is the embedding). Traced once, inside one jit,
    by the harness."""
    s = _sizes(cfg)
    D, F, V, di, N, Hm, W = (s[k] for k in ("D", "F", "V", "di", "N", "Hm",
                                             "W"))
    keys = iter(jax.random.split(key, 2 + 12 * s["L"]))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / jnp.sqrt(
            jnp.float32(fan_in))

    def mixer(kind):
        if kind == "attn":
            q, kv = s["Hq"] * s["hd"], s["KV"] * s["hd"]
            return {"wq": dense((D, 1, q), D), "wkv": dense((D, 1, 2 * kv), D),
                    "wo": dense((1, q, D), q)}
        u = jax.random.uniform(next(keys), (1, Hm))
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        return {
            "w_zx": dense((D, 1, 2 * di), D),
            "w_bc": dense((D, 2 * N), D),
            "w_dt": dense((D, 1, Hm), D),
            "conv_x": dense((1, W, di), W),
            "conv_bc": dense((W, 2 * N), W),
            "conv_x_bias": dense((1, di), W),
            "conv_bc_bias": dense((2 * N,), W),
            "A_log": jnp.log(jnp.arange(1, Hm + 1, dtype=jnp.float32))[None],
            "D_skip": jnp.ones((1, Hm), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.zeros((1, di), jnp.float32),
            "w_out": dense((1, di, D), di),
        }

    def layer(kind):
        return {"norm1": jnp.zeros((D,), jnp.float32), kind: mixer(kind),
                "norm2": jnp.zeros((D,), jnp.float32),
                "mlp": {"w_gate": dense((D, 1, F), D),
                        "w_up": dense((D, 1, F), D),
                        "w_down": dense((1, F, D), F)}}

    return {
        "embed": dense((1, V, D), D),
        "layers": tuple(layer(k) for k in s["kinds"]),
        "final_norm": jnp.zeros((D,), jnp.float32),
    }


def _program_loss(cfg, compute_dtype):
    from repro.models import model as model_lib
    from repro.models.common import ParallelCtx

    mcfg = model_config(cfg)

    def loss(params, batch):
        total, _ = model_lib.loss_fn(params, mcfg, ParallelCtx(), batch,
                                     remat=True, compute_dtype=compute_dtype)
        return total

    return loss


def program_loss(cfg):
    """The system under test: the program's LM loss at float32 compute,
    each layer recomputed in the backward pass."""
    return _program_loss(cfg, jnp.float32)


def control_loss(cfg):
    """The program's own lower-precision path: its loss with a bfloat16
    compute dtype."""
    return _program_loss(cfg, jnp.bfloat16)


# -- plain reference -----------------------------------------------------------


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
            ).astype(x.dtype)


def _causal_conv_silu(x, w, b):
    """Depthwise causal convolution over the sequence, plus its bias, then
    SiLU. x: (B, S, C); w: (W, C); b: (C,)."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + S] * w[i] for i in range(W))
    return jax.nn.silu(out + b)


def _mamba(p, s, x):
    """The Mamba2 mixer on normalized input x: (B, S, D)."""
    dtype = x.dtype
    di, N, H, P = s["di"], s["N"], s["Hm"], s["P"]
    zx = jnp.einsum("bsd,dc->bsc", x, p["w_zx"][:, 0].astype(dtype))
    z, xs = zx[..., :di], zx[..., di:]
    bc = jnp.einsum("bsd,dc->bsc", x, p["w_bc"].astype(dtype))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, p["w_dt"][:, 0].astype(dtype))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][0])
    xs = _causal_conv_silu(xs, p["conv_x"][0].astype(dtype),
                           p["conv_x_bias"][0].astype(dtype))
    bc = _causal_conv_silu(bc, p["conv_bc"].astype(dtype),
                           p["conv_bc_bias"].astype(dtype))
    A = -jnp.exp(p["A_log"][0].astype(jnp.float32))
    b, S, _ = x.shape
    xh = xs.reshape(b, S, H, P)
    y = _SSD(xh, dt, A, bc[..., :N], bc[..., N:], s["Q"])
    y = y + p["D_skip"][0].astype(jnp.float32)[None, None, :, None] * xh.astype(
        jnp.float32)
    y = y.reshape(b, S, di).astype(dtype)
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + s["eps"])
    y = (g * (1.0 + p["norm"][0].astype(jnp.float32))).astype(dtype)
    return jnp.einsum("bsc,cd->bsd", y, p["w_out"][0].astype(dtype))


def _attention(p, s, x):
    """Causal GQA attention with no position embedding on normalized input
    x: (B, S, D). Query head i reads KV head i // (Hq / KV); the KV
    projection holds each KV head's key, then its value."""
    dtype = x.dtype
    b, S, _ = x.shape
    Hq, KV, hd = s["Hq"], s["KV"], s["hd"]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"][:, 0].astype(dtype))
    kv = jnp.einsum("bsd,dh->bsh", x, p["wkv"][:, 0].astype(dtype))
    q = q.reshape(b, S, KV, Hq // KV, hd)
    kv = kv.reshape(b, S, KV, 2, hd)
    k, v = kv[..., 0, :], kv[..., 1, :]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores * s["scale"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v).reshape(b, S, Hq * hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"][0].astype(dtype))


def _swiglu(p, x):
    dtype = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"][:, 0].astype(dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"][:, 0].astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                      p["w_down"][0].astype(dtype))


def _layer(s, kind, lp, x):
    mix = _mamba if kind == "ssm" else _attention
    x = x + s["res"] * mix(lp[kind], s, _rms_norm(x, lp["norm1"], s["eps"]))
    return x + s["res"] * _swiglu(lp["mlp"],
                                  _rms_norm(x, lp["norm2"], s["eps"]))


def reference_logits(cfg, params, tokens):
    """Logits (B, S, V) over the (padded) vocabulary slice, in the dtype of
    ``params``."""
    s = _sizes(cfg)
    table = params["embed"][0]
    x = s["emb"] * jnp.take(table, tokens, axis=0)
    for kind, lp in zip(s["kinds"], params["layers"]):
        x = jax.checkpoint(lambda lp, x, kind=kind: _layer(s, kind, lp, x))(
            lp, x)
    h = _rms_norm(x, params["final_norm"], s["eps"])
    logits = jnp.einsum("bsd,vd->bsv", h, table).astype(jnp.float32)
    return logits / s["logit"]


def reference_loss(cfg, params, batch):
    """Mean next-token cross-entropy over the vocabulary slice."""
    logits = reference_logits(cfg, params, batch["tokens"])
    labels = batch["labels"]
    tgt = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                              axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jax.nn.logsumexp(logits, axis=-1) - tgt
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def model_flops_per_round(cfg, data, cohort) -> float:
    """Forward plus backward FLOPs of the cohort's gradients, from shapes
    (``data``: the traffic's ``tokens`` sizes), 2 per multiply-add forward
    and twice that backward, no recomputation counted: each Mamba2 layer's
    projections, convolution and SSD contractions; each attention layer's
    projections and causal attention (each query against its own prefix,
    scores and values); every layer's SwiGLU MLP; the tied head. The
    embedding is a gather and counts nothing."""
    s = _sizes(cfg)
    D, F, V, di, N, H, P, W = (s[k] for k in ("D", "F", "V", "di", "N", "Hm",
                                               "P", "W"))
    S = data["seq_len"]
    Q = min(s["Q"], S)
    mlp = 3 * D * F
    mamba = (D * (2 * di + 2 * N + H) + di * D + W * (di + 2 * N)
             + Q * N + Q * H * P + 2 * H * P * N)
    q, kv = s["Hq"] * s["hd"], s["KV"] * s["hd"]
    # sum over positions t = 1..S of t keys, twice (scores, values)
    attn = D * (q + 2 * kv) + q * D + q * (S + 1)
    layers = sum(mlp + (mamba if k == "ssm" else attn) for k in s["kinds"])
    per_token = 2 * (layers + D * V) * 3
    return float(per_token * S * data["batch"] * cohort)
