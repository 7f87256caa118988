"""Granite 4.0-H Micro (ibm-granite/granite-4.0-h-micro, config.json,
``model_type`` granitemoehybrid) — Mamba-2 + NoPE GQA hybrid, no experts.

40L, d_model=2048, vocab=100352 (tied). Attention (32 heads, kv=8, head
64, no positional encoding) at layers 5, 15, 25, 35: period 10, offset 5;
the other 36 layers are Mamba-2 (64 heads of 64, d_state 128, one group,
conv 4 with bias, chunk 256). A dense SwiGLU MLP of 8192 in every layer.
Granite multipliers: embedding x12, residual x0.22, attention scale
1/64, logits /8. RMSNorm eps 1e-5.
"""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    arch_type="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    hybrid_period=10,
    hybrid_attn_offsets=(5,),
    ssm=SSMConfig(state_dim=128, conv_dim=4, expand=2, head_dim=64,
                  n_groups=1, chunk_size=256),
    mlp_act="swiglu",
    tie_embeddings=True,
    norm_eps=1e-5,
    position_embedding_type="nope",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)
