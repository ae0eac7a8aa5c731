"""granite-4.0-h-micro (Mamba2 and NoPE GQA mixers, a SwiGLU MLP after
each, muP multipliers, a tied head) through the program's own
``models/model.py`` loss, against:

* the benchmark's plain ``jax.numpy`` reference
  (``bench/configs/granite_4_0_h_micro.py``): loss and every gradient leaf;
* ``transformers``' ``GraniteMoeHybridForCausalLM`` with the program's
  weights copied in: the logits.

Small widths on the CPU, the cell's layers (three Mamba2, then attention),
four SSD chunks, seeded random weights with the norms moved off their
initial value. Also: the mamba2-370m reference at its ``TINY`` sizes
still matches the program.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench")
if BENCH not in sys.path:
    sys.path.append(BENCH)  # the model files import the harness's plugins

import plugins  # noqa: E402

GRANITE = plugins.load_path(os.path.join(BENCH, "configs",
                                         "granite_4_0_h_micro.py"))
MAMBA2 = plugins.load_path(os.path.join(BENCH, "configs", "mamba2_370m.py"))

# Program against reference, both float32 at the highest matmul precision:
# they differ only in the order of float32 sums (the program's chunked
# attention and loss, its grouped contractions), which moves the loss by
# about 1.5e-7 of itself and a gradient leaf by at most about 3e-6 of its
# norm. The program's bfloat16 compute moves the loss by about 5e-6 and the
# worst leaf by about 4e-2, so it fails both.
LOSS_RTOL = 2e-6
GRAD_RTOL = 1e-4
SEQ, BATCH = 32, 2  # four SSD chunks of 8


def _json(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _granite_cfg():
    cfg = _json("granite_4_0_h_micro")
    cfg.update(GRANITE.TINY, vocab_size=512)
    return cfg


def _mamba2_cfg():
    cfg = _json("mamba2_370m")
    cfg.update(MAMBA2.TINY)
    return cfg


def _weights(model, cfg, seed=3):
    """The model file's seeded weights, every norm moved off zero."""
    w = jax.jit(lambda k: model.init_weights(cfg, k))(jax.random.key(seed))
    leaves = jax.tree_util.tree_leaves_with_path(w)
    keys = iter(jax.random.split(jax.random.key(seed + 1), len(leaves)))

    def perturb(path, x):
        k = next(keys)
        if "norm" in jax.tree_util.keystr(path):
            return x + 0.1 * jax.random.normal(k, x.shape)
        return x

    return jax.tree_util.tree_map_with_path(perturb, w)


def _batch(vocab, seed=0):
    seq = np.random.default_rng(seed).integers(0, vocab, (BATCH, SEQ + 1),
                                               dtype=np.int32)
    return {"tokens": jnp.asarray(seq[:, :-1]),
            "labels": jnp.asarray(seq[:, 1:])}


def _gaps(loss, ref, params, batch):
    """(loss gap, worst gradient leaf's gap), each relative, and that
    leaf's path."""
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(loss))(params, batch)
        lr, gr = jax.jit(jax.value_and_grad(ref))(params, batch)
    worst, where = 0.0, None
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree_util.tree_leaves(gr)):
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        if rel > worst:
            worst, where = rel, jax.tree_util.keystr(path)
    return abs(float(lp) - float(lr)) / abs(float(lr)), worst, where


@pytest.mark.parametrize("seed", [3, 11])
def test_program_matches_reference(seed):
    cfg = _granite_cfg()
    params = _weights(GRANITE, cfg, seed)
    loss_gap, grad_gap, where = _gaps(
        GRANITE.program_loss(cfg),
        lambda p, b: GRANITE.reference_loss(cfg, p, b), params,
        _batch(cfg["vocab_size"], seed))
    assert loss_gap <= LOSS_RTOL
    assert grad_gap <= GRAD_RTOL, where


def test_bfloat16_control_fails():
    cfg = _granite_cfg()
    loss_gap, grad_gap, _ = _gaps(
        GRANITE.control_loss(cfg),
        lambda p, b: GRANITE.reference_loss(cfg, p, b),
        _weights(GRANITE, cfg), _batch(cfg["vocab_size"]))
    assert loss_gap > LOSS_RTOL or grad_gap > GRAD_RTOL


def test_mamba2_reference_still_matches():
    cfg = _mamba2_cfg()
    loss_gap, grad_gap, where = _gaps(
        MAMBA2.program_loss(cfg),
        lambda p, b: MAMBA2.reference_loss(cfg, p, b),
        _weights(MAMBA2, cfg), _batch(cfg["vocab_size"]))
    assert loss_gap <= LOSS_RTOL
    assert grad_gap <= GRAD_RTOL, where


def _hf_model(cfg, params):
    """``GraniteMoeHybridForCausalLM`` at these sizes, built on the CPU from
    a config (nothing downloaded), with the program's weights copied in.
    The program's RMSNorms scale by ``1 + w``; transformers' by ``w``."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    s = GRANITE._sizes(cfg)
    L = len(s["kinds"])
    hf_cfg = tf.GraniteMoeHybridConfig(
        vocab_size=s["V"], hidden_size=s["D"],
        intermediate_size=cfg["intermediate_size"],
        shared_intermediate_size=s["F"], num_hidden_layers=L,
        num_attention_heads=s["Hq"], num_key_value_heads=s["KV"],
        layer_types=["attention" if k == "attn" else "mamba"
                     for k in s["kinds"]], mamba_n_heads=s["Hm"],
        mamba_d_head=s["P"], mamba_d_state=s["N"], mamba_n_groups=1,
        mamba_expand=cfg["mamba_expand"], mamba_d_conv=s["W"],
        mamba_chunk_size=s["Q"], mamba_conv_bias=True, mamba_proj_bias=False,
        rms_norm_eps=s["eps"], embedding_multiplier=s["emb"],
        residual_multiplier=s["res"], logits_scaling=s["logit"],
        attention_multiplier=s["scale"], position_embedding_type="nope",
        num_local_experts=0, num_experts_per_tok=0, tie_word_embeddings=True,
        attention_bias=False, hidden_act="silu",
        attn_implementation="eager")
    model = tf.GraniteMoeHybridForCausalLM(hf_cfg).eval()
    t = lambda a: torch.from_numpy(np.array(a, np.float32))
    one = lambda w: t(1.0 + np.asarray(w))
    D, hd, KV = s["D"], s["hd"], s["KV"]
    state = {"model.embed_tokens.weight": t(params["embed"][0]),
             "lm_head.weight": t(params["embed"][0]),
             "model.norm.weight": one(params["final_norm"])}
    for i, (kind, lp) in enumerate(zip(s["kinds"], params["layers"])):
        pre = f"model.layers.{i}."
        state[pre + "input_layernorm.weight"] = one(lp["norm1"])
        state[pre + "post_attention_layernorm.weight"] = one(lp["norm2"])
        m = lp["mlp"]
        state[pre + "shared_mlp.input_linear.weight"] = t(np.concatenate(
            [m["w_gate"][:, 0], m["w_up"][:, 0]], axis=1).T)
        state[pre + "shared_mlp.output_linear.weight"] = t(m["w_down"][0].T)
        if kind == "attn":
            a = lp["attn"]
            kv = np.asarray(a["wkv"][:, 0]).reshape(D, KV, 2, hd)
            state[pre + "self_attn.q_proj.weight"] = t(a["wq"][:, 0].T)
            state[pre + "self_attn.k_proj.weight"] = t(
                kv[:, :, 0].reshape(D, KV * hd).T)
            state[pre + "self_attn.v_proj.weight"] = t(
                kv[:, :, 1].reshape(D, KV * hd).T)
            state[pre + "self_attn.o_proj.weight"] = t(a["wo"][0].T)
            continue
        p = lp["ssm"]
        # in_proj rows: z, x, B, C, dt; conv1d channels: x, B, C
        state[pre + "mamba.in_proj.weight"] = t(np.concatenate(
            [p["w_zx"][:, 0], p["w_bc"], p["w_dt"][:, 0]], axis=1).T)
        state[pre + "mamba.conv1d.weight"] = t(np.concatenate(
            [p["conv_x"][0], p["conv_bc"]], axis=1).T[:, None, :])
        state[pre + "mamba.conv1d.bias"] = t(np.concatenate(
            [p["conv_x_bias"][0], p["conv_bc_bias"]]))
        state[pre + "mamba.dt_bias"] = t(p["dt_bias"][0])
        state[pre + "mamba.A_log"] = t(p["A_log"][0])
        state[pre + "mamba.D"] = t(p["D_skip"][0])
        state[pre + "mamba.norm.weight"] = one(p["norm"][0])
        state[pre + "mamba.out_proj.weight"] = t(p["w_out"][0].T)
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and not [k for k in missing if k != "lm_head.weight"]
    return model


def test_logits_match_transformers():
    """The program's logits (its hidden state through the tied head, over
    the logits scale) against transformers' forward of the same weights.
    Both float32; torch's SSD sums in another order (a segment-sum form),
    which moves a logit by a few 1e-6 of the largest; 1e-4 of it holds
    that, and the program's bfloat16 path misses it by far."""
    import torch

    from repro.models import model as model_lib
    from repro.models.common import ParallelCtx

    cfg = _granite_cfg()
    params = _weights(GRANITE, cfg)
    model = _hf_model(cfg, params)
    tokens = _batch(cfg["vocab_size"])["tokens"]
    mcfg = GRANITE.model_config(cfg)

    def logits(dtype):
        with jax.default_matmul_precision("highest"):
            out = jax.jit(lambda p, t: jnp.einsum(
                "bsd,dv->bsv",
                model_lib.forward_hidden(p, mcfg, ParallelCtx(), t,
                                         compute_dtype=dtype)[0],
                model_lib._head(p).astype(dtype)))(params, tokens)  # tied
        return np.asarray(out.astype(jnp.float32) / mcfg.logits_scaling)

    with torch.no_grad():
        want = model(torch.from_numpy(np.asarray(tokens)).long()).logits
    want = want.numpy()
    scale = np.max(np.abs(want))
    assert np.max(np.abs(logits(jnp.float32) - want)) <= 1e-4 * scale
    assert np.max(np.abs(logits(jnp.bfloat16) - want)) > 1e-4 * scale
