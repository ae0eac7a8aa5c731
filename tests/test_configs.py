"""Guard the assigned architecture configs against drift: every number from
the assignment table is asserted here."""
import pytest

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCH_IDS, all_configs, get_config

# (layers, d_model, heads, kv, d_ff, vocab, arch_type)
ASSIGNMENT = {
    "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000, "dense"),
    "gemma3-4b": (34, 2560, 8, 4, 10240, 262144, "dense"),
    "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000, "hybrid"),
    "mamba2-370m": (48, 1024, 0, 0, 0, 50280, "ssm"),
    "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064, "moe"),
    "musicgen-medium": (48, 1536, 24, 24, 6144, 2048, "audio"),
    "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000, "dense"),
    "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936, "moe"),
    "pixtral-12b": (40, 5120, 32, 8, 14336, 131072, "vlm"),
    "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024, "dense"),
    "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352, "hybrid"),
}

MOE = {"phi3.5-moe-42b-a6.6b": (16, 2), "qwen3-moe-30b-a3b": (128, 8)}
SSM_STATE = {"zamba2-1.2b": 64, "mamba2-370m": 128, "granite-4.0-h-micro": 128}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_assignment_numbers(arch):
    L, D, H, KV, FF, V, T = ASSIGNMENT[arch]
    cfg = get_config(arch)
    assert cfg.num_layers == L and len(cfg.layers) == L
    assert cfg.d_model == D
    assert cfg.num_heads == H and cfg.num_kv_heads == KV
    assert cfg.d_ff == FF
    assert cfg.vocab_size == V
    assert cfg.arch_type == T


@pytest.mark.parametrize("arch", list(MOE))
def test_moe_numbers(arch):
    cfg = get_config(arch)
    E, k = MOE[arch]
    assert cfg.moe.num_experts == E and cfg.moe.top_k == k
    assert cfg.moe.d_ff_expert == cfg.d_ff


@pytest.mark.parametrize("arch", list(SSM_STATE))
def test_ssm_state(arch):
    cfg = get_config(arch)
    assert cfg.ssm.state_dim == SSM_STATE[arch]


def test_input_shapes():
    s = INPUT_SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)
    assert s["train_4k"].kind == "train"
    assert s["prefill_32k"].kind == "prefill"
    assert s["decode_32k"].kind == "decode"


def test_long_context_eligibility():
    eligible = {a for a in ARCH_IDS if get_config(a).subquadratic}
    assert eligible == {"gemma3-4b", "zamba2-1.2b", "mamba2-370m",
                        "h2o-danube-3-4b"}


def test_gemma_local_global_pattern():
    cfg = get_config("gemma3-4b")
    globals_ = [i for i, l in enumerate(cfg.layers) if l.window is None]
    assert globals_ == [5, 11, 17, 23, 29]  # every 6th of 34
    assert all(cfg.layers[i].window == 1024 for i in range(34) if i not in globals_)


def test_zamba_shared_pattern():
    cfg = get_config("zamba2-1.2b")
    shared = [i for i, l in enumerate(cfg.layers) if l.kind == "shared_attn"]
    assert shared == [5, 11, 17, 23, 29, 35]
    assert cfg.shared_attn


def test_granite_hybrid_pattern():
    """config.json's layer_types, NoPE, the muP multipliers, the tied head."""
    cfg = get_config("granite-4.0-h-micro")
    attn = [i for i, l in enumerate(cfg.layers) if l.kind == "attn"]
    assert attn == [5, 15, 25, 35]
    assert cfg.ssm_mlp and cfg.tie_embeddings and cfg.rotary_frac == 0.0
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 8.0)
    assert cfg.attn_spec(cfg.layers[5]).scale == 0.015625
    assert cfg.norm_eps == 1e-5
    s = cfg.ssm
    assert (s.num_heads, s.head_dim, s.d_inner, s.conv_width, s.chunk) == (
        64, 64, 4096, 4, 256)
    assert s.conv_bias


def test_vocab_padding():
    cfg = get_config("mamba2-370m")
    assert cfg.vocab_size == 50280
    assert cfg.padded_vocab(16) % (16 * 128) == 0
    assert cfg.padded_vocab(16) >= 50280


def test_all_reduced_configs_exist():
    for arch, cfg in all_configs(reduced=True).items():
        assert cfg.num_layers <= 2
        assert cfg.d_model <= 512
