"""IBM Granite 4.0-H Small (32B-A9B) — hybrid Mamba-2 + NoPE GQA, 72-expert
MoE with a shared expert in every layer.
[hf:ibm-granite/granite-4.0-h-small config.json]"""
from repro.configs.base import ArchConfig, MoEConfig, SSMConfig

CONFIG = ArchConfig(
    arch_id="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=0,                 # no dense MLP: every layer is MoE + shared
    vocab_size=100352,
    moe=MoEConfig(n_experts=72, top_k=10, d_ff_expert=768, d_ff_shared=1536),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, chunk_size=256,
                  n_groups=1, conv_kernel=4),
    attn_period=10,         # layer_types: attention at 5, 15, 25, 35
    attn_index=5,
    nope=True,
    attn_scale=1 / 128,     # attention_multiplier
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_embeddings=True,
    subquadratic=True,
    source="hf:ibm-granite/granite-4.0-h-small/config.json",
)
