"""granite-4.0-h-micro [granite_hybrid] — 40 layers, Mamba2 with GQA
attention at layers 5, 15, 25 and 35, each mixer followed by its own
SwiGLU MLP; NoPE; Granite's embedding, residual, attention and logits
multipliers; tied embedding.
[hf:ibm-granite/granite-4.0-h-micro config.json]
"""
from repro.configs.base import ModelConfig, smoke_reduce

ATTENTION_LAYERS = (5, 15, 25, 35)


def layer_types(n: int = 40) -> tuple:
    """The published layer_types, cut to the first ``n`` layers."""
    return tuple("attention" if i in ATTENTION_LAYERS else "mamba"
                 for i in range(n))


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro",
        family="granite_hybrid",
        num_layers=40,
        d_model=2048,
        num_heads=32,
        num_kv_heads=8,
        d_ff=8192,                # shared_intermediate_size
        vocab_size=100352,
        ssm_state=128,
        ssm_headdim=64,
        ssm_expand=2,             # 64 Mamba2 heads of 64
        conv_width=4,
        layer_types=layer_types(),
        norm_eps=1e-5,
        tie_embeddings=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        attention_multiplier=0.015625,
        logits_scaling=8.0,
        use_rope=False,
        source="hf:ibm-granite/granite-4.0-h-micro",
    )


def smoke_config() -> ModelConfig:
    """Two layers, one of each kind."""
    return smoke_reduce(config(), ssm_headdim=32, ssm_state=16,
                        layer_types=("mamba", "attention"))
