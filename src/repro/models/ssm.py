"""Mamba2 (state-space duality / SSD) blocks — training scan + decode step.

TPU adaptation: the SSD chunked algorithm (Dao & Gu, 2024) is expressed as
dense einsums per chunk (intra-chunk "attention-like" quadratic form +
inter-chunk state recurrence via lax.scan over chunks), which maps onto the
MXU; there is no per-timestep recurrence on the training path.

Sharding: the inner dimension (d_inner = expand * d_model, split into heads
of size head_dim) is sharded over the model axis — in/out projections are
column/row-parallel like an MLP. B/C/dt projections are per-head or shared
(ngroups=1), with the shared B/C projection REPLICATED (sync=tp). The only
collective per block is the out-projection psum.

Decode is the O(1) recurrent update h' = exp(A dt) h + dt * (B ⊗ x).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import squeeze_tp
from repro.models.common import ParallelCtx, dense_init


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_model: int
    state_dim: int          # N
    head_dim: int = 64      # P (mamba2 convention)
    expand: int = 2
    conv_width: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    conv_bias: bool = False  # the depthwise convolutions add a bias

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def num_heads(self) -> int:
        return self.d_inner // self.head_dim

    def heads_local(self, tp: int) -> int:
        if self.num_heads % tp != 0:
            raise ValueError(f"ssm heads {self.num_heads} not divisible by tp={tp}")
        return self.num_heads // tp


def init_params(key, spec: SSMSpec, tp: int, dtype=jnp.float32):
    h_l = spec.heads_local(tp)
    di_l = h_l * spec.head_dim
    D, N, W = spec.d_model, spec.state_dim, spec.conv_width
    ks = jax.random.split(key, 7)
    dt = jnp.exp(
        jax.random.uniform(ks[4], (tp, h_l))
        * (jnp.log(spec.dt_max) - jnp.log(spec.dt_min))
        + jnp.log(spec.dt_min)
    )
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    p = {
        # z (gate) and x streams, head-sharded
        "w_zx": dense_init(ks[0], (D, tp, 2 * di_l), dtype=dtype),
        # shared B and C projections (ngroups=1): replicated
        "w_bc": dense_init(ks[1], (D, 2 * N), dtype=dtype),
        "w_dt": dense_init(ks[2], (D, tp, h_l), dtype=dtype),
        "conv_x": dense_init(ks[3], (tp, W, di_l), in_axis=1, dtype=dtype),
        "conv_bc": dense_init(ks[5], (W, 2 * N), in_axis=0, dtype=dtype),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, h_l + 1, dtype=jnp.float32)[None], (tp, 1))),
        "D_skip": jnp.ones((tp, h_l), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": jnp.zeros((tp, di_l), dtype),
        "w_out": dense_init(ks[6], (tp, di_l, D), in_axis=1, dtype=dtype),
    }
    if spec.conv_bias:
        p["conv_x_bias"] = jnp.zeros((tp, di_l), dtype)
        p["conv_bc_bias"] = jnp.zeros((2 * N,), dtype)
    return p


def param_meta(spec: SSMSpec, tp: int, dtype=jnp.float32):
    from repro.models.meta import Meta

    h_l = spec.heads_local(tp)
    di_l = h_l * spec.head_dim
    D, N, W = spec.d_model, spec.state_dim, spec.conv_width
    m = {
        "w_zx": Meta((D, tp, 2 * di_l), dtype, P(None, "model", None), 1),
        "w_bc": Meta((D, 2 * N), dtype, P(None, None), tp),
        "w_dt": Meta((D, tp, h_l), dtype, P(None, "model", None), 1),
        "conv_x": Meta((tp, W, di_l), dtype, P("model", None, None), 1),
        "conv_bc": Meta((W, 2 * N), dtype, P(None, None), tp),
        "A_log": Meta((tp, h_l), jnp.float32, P("model", None), 1),
        "D_skip": Meta((tp, h_l), jnp.float32, P("model", None), 1),
        "dt_bias": Meta((tp, h_l), jnp.float32, P("model", None), 1),
        "norm": Meta((tp, di_l), dtype, P("model", None), 1),
        "w_out": Meta((tp, di_l, D), dtype, P("model", None, None), 1),
    }
    if spec.conv_bias:
        m["conv_x_bias"] = Meta((tp, di_l), dtype, P("model", None), 1)
        m["conv_bc_bias"] = Meta((2 * N,), dtype, P(None), tp)
    return m


def _gated_rms_norm(y, z, w, ctx: ParallelCtx, eps: float = 1e-6):
    """Mamba2's RMSNormGated over the FULL d_inner dimension, which is
    head-sharded here: the mean-square is psum'd over the model axis."""
    x = (y * jax.nn.silu(z)).astype(jnp.float32)
    local = x.shape[-1]
    total = ctx.psum_model(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    denom = local * (ctx.tp if ctx.model_axis is not None else 1)
    var = total / denom
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + w.astype(jnp.float32))).astype(y.dtype)


def _depthwise_causal_conv(x, w, b=None):
    """x: (B, S, C); w: (W, C); b: (C,) or None. Depthwise causal conv
    (+ bias) + silu."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(
        jax.lax.dynamic_slice_in_dim(xp, i, x.shape[1], axis=1) * w[i]
        for i in range(W)
    )
    if b is not None:
        out = out + b
    return jax.nn.silu(out)


def _conv_biases(params, dtype):
    """The x and B/C streams' conv biases, or ``(None, None)``."""
    if "conv_x_bias" not in params:
        return None, None
    return (squeeze_tp(params["conv_x_bias"], 0).astype(dtype),
            params["conv_bc_bias"].astype(dtype))


def _project(params, spec: SSMSpec, tp_ctx: ParallelCtx, x):
    """Common projections. x: (B,S,D) -> z,xs:(B,S,di_l), B,C:(B,S,N), dt:(B,S,h_l)."""
    w_zx = squeeze_tp(params["w_zx"], 1).astype(x.dtype)
    zx = jnp.einsum("bsd,dc->bsc", x, w_zx)
    di_l = zx.shape[-1] // 2
    z, xs = zx[..., :di_l], zx[..., di_l:]
    bc = jnp.einsum("bsd,dc->bsc", x, params["w_bc"].astype(x.dtype))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, squeeze_tp(params["w_dt"], 1).astype(x.dtype))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + squeeze_tp(params["dt_bias"], 0))
    return z, xs, bc, dt


def forward(params, spec: SSMSpec, ctx: ParallelCtx, x, *, eps: float = 1e-6,
            return_state: bool = False):
    """Training path (chunked SSD). x: (B, S, D) -> (B, S, D); ``eps``: the
    gated RMSNorm's.

    return_state: also return the decode-ready state dict (final recurrent
    state + raw conv tails) so prefill can hand off to ``decode``.
    """
    B, S, D = x.shape
    N, P_, Q = spec.state_dim, spec.head_dim, min(spec.chunk, x.shape[1])
    if S % Q != 0:
        Q = S  # irregular (small/test) lengths: single chunk
    nC = S // Q
    z, xs, bc, dt = _project(params, spec, ctx, x)
    h_l = dt.shape[-1]
    xs_raw, bc_raw = xs, bc  # pre-conv streams (decode conv state)

    b_x, b_bc = _conv_biases(params, x.dtype)
    xs = _depthwise_causal_conv(xs, squeeze_tp(params["conv_x"], 0).astype(x.dtype), b_x)
    bc = _depthwise_causal_conv(bc, params["conv_bc"].astype(x.dtype), b_bc)
    Bm, Cm = bc[..., :N], bc[..., N:]

    A = -jnp.exp(squeeze_tp(params["A_log"], 0))  # (h_l,) negative
    xh = xs.reshape(B, nC, Q, h_l, P_)
    dt_c = dt.reshape(B, nC, Q, h_l)
    B_c = Bm.reshape(B, nC, Q, N).astype(jnp.float32)
    C_c = Cm.reshape(B, nC, Q, N).astype(jnp.float32)

    da = dt_c * A  # (B, nC, Q, h)  log-decay increments
    cum = jnp.cumsum(da, axis=2)  # within-chunk inclusive cumsum
    # intra-chunk: y[i] = sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) dt_j x_j
    decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nC,Q_i,Q_j,h)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(mask[None, None, :, :, None], decay, -jnp.inf)
    cb = jnp.einsum("bcin,bcjn->bcij", C_c, B_c)  # (B,nC,Q,Q)
    attn = cb[..., None] * jnp.exp(decay)  # (B,nC,Q,Q,h)
    y_intra = jnp.einsum(
        "bcijh,bcjh,bcjhp->bcihp", attn, dt_c, xh.astype(jnp.float32)
    )

    # chunk states: S_c = sum_j exp(cum_end - cum_j) dt_j x_j B_j^T  (h,P,N)
    seg = cum[:, :, -1:, :] - cum  # decay from j to end of chunk
    states = jnp.einsum(
        "bcjh,bcjh,bcjhp,bcjn->bchpn",
        jnp.exp(seg), dt_c, xh.astype(jnp.float32), B_c,
    )
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nC,h) whole-chunk decay

    def scan_fn(h_prev, inp):
        s_c, dec = inp  # (B,h,P,N), (B,h)
        h_new = h_prev * dec[..., None, None] + s_c
        return h_new, h_prev

    h0 = jnp.zeros((B, h_l, P_, N), jnp.float32)
    h_final, h_before = jax.lax.scan(
        scan_fn,
        h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_before = jnp.moveaxis(h_before, 0, 1)  # (B,nC,h,P,N) state entering chunk

    # inter-chunk: y_inter[i] = exp(cum_i) * C_i . h_entering
    y_inter = jnp.einsum(
        "bcin,bchpn,bcih->bcihp", C_c, h_before, jnp.exp(cum)
    )

    y = (y_intra + y_inter).reshape(B, S, h_l, P_)
    y = y + squeeze_tp(params["D_skip"], 0)[None, None, :, None] * xs.reshape(
        B, S, h_l, P_
    ).astype(jnp.float32)
    y = y.reshape(B, S, h_l * P_).astype(x.dtype)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = _gated_rms_norm(y, z, squeeze_tp(params["norm"], 0), ctx, eps)
    out = jnp.einsum("bsc,cd->bsd", y, squeeze_tp(params["w_out"], 0).astype(y.dtype))
    out = ctx.sp_scatter(out)
    if not return_state:
        return out
    W = spec.conv_width
    state = {
        "h": h_final[:, None],  # (B, 1(tp), h, P, N)
        "conv_x": xs_raw[:, S - (W - 1):][:, None],
        "conv_bc": bc_raw[:, S - (W - 1):],
    }
    return out, state


def init_state_shape(spec: SSMSpec, tp: int, batch: int):
    h_l = spec.heads_local(tp)
    return {
        "h": (batch, tp, h_l, spec.head_dim, spec.state_dim),
        "conv_x": (batch, tp, spec.conv_width - 1, h_l * spec.head_dim),
        "conv_bc": (batch, spec.conv_width - 1, 2 * spec.state_dim),
    }


def decode(params, spec: SSMSpec, ctx: ParallelCtx, x, state, *,
           eps: float = 1e-6):
    """One recurrent decode step. x: (B, 1, D); state per init_state_shape."""
    B = x.shape[0]
    N, P_ = spec.state_dim, spec.head_dim
    z, xs, bc, dt = _project(params, spec, ctx, x)  # seq dim = 1
    h_l = dt.shape[-1]

    # rolling conv buffers
    conv_x_buf = squeeze_tp(state["conv_x"], 1)  # (B, W-1, di_l)
    xs_hist = jnp.concatenate([conv_x_buf, xs], axis=1)  # (B, W, di_l)
    w_cx = squeeze_tp(params["conv_x"], 0).astype(x.dtype)
    xs_t = jnp.einsum("bwc,wc->bc", xs_hist, w_cx)
    bc_hist = jnp.concatenate([state["conv_bc"], bc], axis=1)
    bc_t = jnp.einsum("bwc,wc->bc", bc_hist, params["conv_bc"].astype(x.dtype))
    b_x, b_bc = _conv_biases(params, x.dtype)
    if b_x is not None:
        xs_t, bc_t = xs_t + b_x, bc_t + b_bc
    xs_t = jax.nn.silu(xs_t)[:, None]
    bc_t = jax.nn.silu(bc_t)[:, None]
    Bm, Cm = bc_t[..., :N], bc_t[..., N:]

    A = -jnp.exp(squeeze_tp(params["A_log"], 0))
    dt_t = dt[:, 0]  # (B, h)
    xh = xs_t.reshape(B, h_l, P_).astype(jnp.float32)
    dec = jnp.exp(dt_t * A)  # (B, h)
    h_prev = squeeze_tp(state["h"], 1)  # (B, h, P, N)
    h_new = h_prev * dec[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt_t, xh, Bm[:, 0].astype(jnp.float32)
    )
    y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(jnp.float32), h_new)
    y = y + squeeze_tp(params["D_skip"], 0)[None, :, None] * xh
    y = y.reshape(B, 1, h_l * P_).astype(x.dtype)
    y = _gated_rms_norm(y, z, squeeze_tp(params["norm"], 0), ctx, eps)
    out = jnp.einsum("bsc,cd->bsd", y, squeeze_tp(params["w_out"], 0).astype(y.dtype))
    out = ctx.psum_model(out)
    new_state = {
        "h": h_new[:, None],
        "conv_x": xs_hist[:, 1:][:, None],
        "conv_bc": bc_hist[:, 1:],
    }
    return out, new_state
