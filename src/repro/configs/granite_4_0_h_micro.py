"""Granite 4.0-H Micro [hybrid]: 40 layers, 36 Mamba2 and 4 full GQA
attention (NoPE), each mixer followed by a SwiGLU MLP; muP-style embedding,
residual and logit multipliers; the LM head tied to the embedding.
[https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json]
"""
from repro.configs.base import LayerSpec, ModelConfig
from repro.models.ssm import SSMSpec

SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
# config.json's layer_types: attention at 5, 15, 25 and 35
ATTENTION_AT = (5, 15, 25, 35)


def layers(n: int, attention_at=ATTENTION_AT):
    return tuple(LayerSpec("attn" if i in attention_at else "ssm")
                 for i in range(n))


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro",
        arch_type="hybrid",
        source=SOURCE,
        num_layers=40,
        d_model=2048,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab_size=100352,
        layers=layers(40),
        mlp_kind="swiglu",
        rotary_frac=0.0,  # position_embedding_type "nope"
        ssm=SSMSpec(d_model=2048, state_dim=128, head_dim=64, expand=2,
                    conv_width=4, chunk=256, conv_bias=True),
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm_mlp=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        attn_scale=0.015625,
    )


def reduced_config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro-reduced",
        arch_type="hybrid",
        source=SOURCE,
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        d_ff=512,
        vocab_size=512,
        layers=layers(2, attention_at=(1,)),
        mlp_kind="swiglu",
        rotary_frac=0.0,
        ssm=SSMSpec(d_model=256, state_dim=16, head_dim=32, expand=2,
                    chunk=32, conv_bias=True),
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm_mlp=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        attn_scale=0.015625,
        q_chunk=64,
    )
