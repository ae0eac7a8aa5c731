"""mamba2-370m at its published widths, depth cut to 4 of 48 layers: what the
program runs, and a plain reference of the same network.

Every layer of Mamba2 is the same SSD block, so four layers are whole
periods of the layer pattern. The program departs from the published
checkpoint in ways the reference follows, since it is compared with the
program as run: the LM head is a separate matrix (the published model ties
it to the embedding), the depthwise convolutions carry no bias, and every
RMSNorm scales by ``1 + w``.

The configuration states float32 parameters and activations at JAX's
default matmul precision. The reference writes the chunked SSD form of
arXiv:2405.21060 (its "minimal SSD" listing: a quadratic form inside each
chunk, a state recurrence across chunks) with the same contractions as the
program, so that at that precision both round the same operands to the
MXU's input width.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

SOURCE = "arXiv:2405.21060; https://huggingface.co/state-spaces/mamba2-370m"
REDUCED = {
    "n_layer": "48 -> 4: all layers are SSD blocks, so 4 are whole periods",
    "tie_embeddings": "true -> false: the program's LM head is its own matrix",
}
# widths small enough for a test run on the CPU (bench/rehearse.py)
TINY = {"d_model": 64, "n_layer": 1, "vocab_size": 500, "d_state": 16,
        "headdim": 16, "chunk_size": 8}
ASSUMED = {
    "tokens": "uniform over the 50,277 ids, labels the next token, per traffic",
    "num_clients": "256 clients; it sets only the staged population",
    "mechanism": "rqm c=0.02, m=16, q=0.42 as in the paper's EMNIST runs",
}


def _sizes(cfg):
    D = cfg["d_model"]
    di = cfg["expand"] * D
    mult = cfg["pad_vocab_size_multiple"]
    return {
        "D": D, "di": di, "N": cfg["d_state"], "P": cfg["headdim"],
        "H": di // cfg["headdim"], "W": cfg["d_conv"],
        "V": -(-cfg["vocab_size"] // mult) * mult, "L": cfg["n_layer"],
        "Q": cfg["chunk_size"],
    }


def model_config(cfg):
    """The program's ModelConfig for these sizes (its published 48-layer
    config, cut to ``n_layer``)."""
    from repro.configs.mamba2_370m import config
    from repro.models.ssm import SSMSpec

    if cfg["ngroups"] != 1:
        raise ValueError("the program shares one B/C group (ngroups=1)")
    base = config()
    return dataclasses.replace(
        base, num_layers=cfg["n_layer"], layers=base.layers[:cfg["n_layer"]],
        d_model=cfg["d_model"], vocab_size=cfg["vocab_size"],
        vocab_pad_to=cfg["pad_vocab_size_multiple"],
        ssm=SSMSpec(d_model=cfg["d_model"], state_dim=cfg["d_state"],
                    head_dim=cfg["headdim"], expand=cfg["expand"],
                    conv_width=cfg["d_conv"], chunk=cfg["chunk_size"]))


def init_weights(cfg, key):
    """Seeded weights in the program's parameter layout (one model shard:
    the leading or middle axis of size 1 is the program's tensor-parallel
    axis). Traced once, inside one jit, by the harness."""
    s = _sizes(cfg)
    D, di, N, H, W, V = s["D"], s["di"], s["N"], s["H"], s["W"], s["V"]
    keys = iter(jax.random.split(key, 2 + 7 * s["L"]))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / jnp.sqrt(
            jnp.float32(fan_in))

    def layer():
        u = jax.random.uniform(next(keys), (1, H))
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        return {"norm1": jnp.zeros((D,), jnp.float32), "ssm": {
            "w_zx": dense((D, 1, 2 * di), D),
            "w_bc": dense((D, 2 * N), D),
            "w_dt": dense((D, 1, H), D),
            "conv_x": dense((1, W, di), W),
            "conv_bc": dense((W, 2 * N), W),
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))[None],
            "D_skip": jnp.ones((1, H), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.zeros((1, di), jnp.float32),
            "w_out": dense((1, di, D), di),
        }}

    return {
        "embed": dense((1, V, D), D),
        "layers": tuple(layer() for _ in range(s["L"])),
        "final_norm": jnp.zeros((D,), jnp.float32),
        "lm_head": dense((D, 1, V), D),
    }


def _program_loss(cfg, compute_dtype):
    from repro.models import model as model_lib
    from repro.models.common import ParallelCtx

    mcfg = model_config(cfg)

    def loss(params, batch):
        total, _ = model_lib.loss_fn(params, mcfg, ParallelCtx(), batch,
                                     remat=False, compute_dtype=compute_dtype)
        return total

    return loss


def program_loss(cfg):
    """The system under test: the program's LM loss, as its ``lm`` task
    calls it (float32 compute, no remat)."""
    return _program_loss(cfg, jnp.float32)


def control_loss(cfg):
    """The program's own lower-precision path: its loss with a bfloat16
    compute dtype."""
    return _program_loss(cfg, jnp.bfloat16)


# -- plain reference -----------------------------------------------------------


def _rms_norm(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
            ).astype(x.dtype)


def _causal_conv_silu(x, w):
    """Depthwise causal convolution over the sequence, then SiLU.
    x: (B, S, C); w: (W, C)."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + S] * w[i] for i in range(W))
    return jax.nn.silu(out)


def _ssd(xs, dt, A, Bm, Cm, Q):
    """Chunked SSD scan: y_t = sum_{s<=t} C_t.B_s exp(sum_{s<r<=t} dt_r A)
    dt_s x_s. xs: (B, S, H, P); dt: (B, S, H); Bm, Cm: (B, S, N)."""
    b, S, H, P = xs.shape
    N = Bm.shape[-1]
    Q = min(Q, S) if S % min(Q, S) == 0 else S
    nC = S // Q
    x = xs.reshape(b, nC, Q, H, P).astype(jnp.float32)
    dtc = dt.reshape(b, nC, Q, H)
    Bc = Bm.reshape(b, nC, Q, N).astype(jnp.float32)
    Cc = Cm.reshape(b, nC, Q, N).astype(jnp.float32)
    cum = jnp.cumsum(dtc * A, axis=2)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)[..., None] * decay
    y_diag = jnp.einsum("bcijh,bcjh,bcjhp->bcihp", scores, dtc, x)
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)
    states = jnp.einsum("bcjh,bcjh,bcjhp,bcjn->bchpn", to_end, dtc, x, Bc)
    chunk_decay = jnp.exp(cum[:, :, -1, :])
    h = jnp.zeros((b, H, P, N), jnp.float32)
    entering = []
    for c in range(nC):
        entering.append(h)
        h = h * chunk_decay[:, c, :, None, None] + states[:, c]
    entering = jnp.stack(entering, axis=1)
    y_off = jnp.einsum("bcin,bchpn,bcih->bcihp", Cc, entering, jnp.exp(cum))
    return (y_diag + y_off).reshape(b, S, H, P)


def _mixer(p, s, x):
    """One Mamba2 mixer on normalized input x: (B, S, D)."""
    dtype = x.dtype
    di, N, H, P = s["di"], s["N"], s["H"], s["P"]
    zx = jnp.einsum("bsd,dc->bsc", x, p["w_zx"][:, 0].astype(dtype))
    z, xs = zx[..., :di], zx[..., di:]
    bc = jnp.einsum("bsd,dc->bsc", x, p["w_bc"].astype(dtype))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, p["w_dt"][:, 0].astype(dtype))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][0])
    xs = _causal_conv_silu(xs, p["conv_x"][0].astype(dtype))
    bc = _causal_conv_silu(bc, p["conv_bc"].astype(dtype))
    A = -jnp.exp(p["A_log"][0].astype(jnp.float32))
    b, S, _ = x.shape
    xh = xs.reshape(b, S, H, P)
    y = _ssd(xh, dt, A, bc[..., :N], bc[..., N:], s["Q"])
    y = y + p["D_skip"][0].astype(jnp.float32)[None, None, :, None] * xh.astype(
        jnp.float32)
    y = y.reshape(b, S, di).astype(dtype)
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + 1e-6)
    y = (g * (1.0 + p["norm"][0].astype(jnp.float32))).astype(dtype)
    return jnp.einsum("bsc,cd->bsd", y, p["w_out"][0].astype(dtype))


def reference_loss(cfg, params, batch):
    """Mean next-token cross-entropy over the (padded) vocabulary, computed
    in the dtype of ``params``."""
    s = _sizes(cfg)
    x = jnp.take(params["embed"][0], batch["tokens"], axis=0)
    for lp in params["layers"]:
        x = x + _mixer(lp["ssm"], s, _rms_norm(x, lp["norm1"]))
    h = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", h,
                        params["lm_head"][:, 0]).astype(jnp.float32)
    labels = batch["labels"]
    tgt = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                              axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jax.nn.logsumexp(logits, axis=-1) - tgt
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def model_flops_per_round(cfg, data, cohort) -> float:
    """Forward plus backward FLOPs of the cohort's gradients, from shapes
    (``data``: the traffic's ``tokens`` sizes):
    the projections, convolutions, SSD contractions and LM head, 2 per
    multiply-add forward and twice that backward. The embedding is a
    gather and counts nothing."""
    s = _sizes(cfg)
    D, di, N, H, P, W, V = (s[k] for k in ("D", "di", "N", "H", "P", "W", "V"))
    Q = min(s["Q"], data["seq_len"])
    proj = D * (2 * di + 2 * N + H) + di * D
    conv = W * (di + 2 * N)
    ssd = Q * N + Q * H * P + 2 * H * P * N
    per_token = 2 * (s["L"] * (proj + conv + ssd) + D * V) * 3
    tokens = data["seq_len"] * data["batch"]
    return float(per_token * tokens * cohort)
