"""granite-4.0-h-small — Mamba-2 + NoPE GQA hybrid with a 72-expert top-10
MoE and a shared expert in every layer. [hf ibm-granite/granite-4.0-h-small]

40 layers, d_model 4096: attention (32 query heads, 8 KV heads, head_dim
128, no positional encoding, softmax scale 1/128) at layers 5, 15, 25 and
35, Mamba-2 (128 heads x 64, d_state 128) elsewhere. Every layer's
feed-forward routes each token to its top 10 of 72 SwiGLU experts of width
768 and adds a shared SwiGLU expert of width 1536. Scalars: embedding x12,
residual x0.22, logits /16. Tied 100352-row vocabulary, 32.2B parameters.
Hybrid → sub-quadratic → long_500k runs.
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    norm_eps=1e-5,
    tie_embeddings=True,
    layer_types=PERIOD * 4,
    use_rope=False,
    attention_multiplier=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    moe=MoEConfig(num_experts=72, top_k=10, shared_d_ff=1536),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, chunk_size=256),
)


def reduced() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-small-smoke",
        family="hybrid",
        num_layers=4,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        d_ff=32,
        vocab_size=256,
        tie_embeddings=True,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        use_rope=False,
        attention_multiplier=1 / 16,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        moe=MoEConfig(num_experts=8, top_k=3, shared_d_ff=48),
        ssm=SSMConfig(d_state=16, head_dim=16, expand=2, chunk_size=16),
    )
