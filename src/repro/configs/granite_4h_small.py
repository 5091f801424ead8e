"""granite-4.0-h-small [hybrid_moe] 40L d_model=4096, 36 Mamba2 + 4 NoPE GQA
layers (32H, kv=8, hd=128), an MoE FFN in every layer (72 SwiGLU experts of
768, top-10, one shared expert of 1536), vocab=100352, tied embeddings.
[hf:ibm-granite/granite-4.0-h-small config.json, model_type
granitemoehybrid]  32.2B parameters, about 8.8B active per token.

Every layer: x + 0.22·mixer(rmsnorm(x)), then
x + 0.22·(moe(rmsnorm(x)) + shared(rmsnorm(x))); the mixer is Mamba2
(128 heads of 64, d_state 128, one group, conv 4, chunk 256, expand 2) or
GQA attention without positions, scores x 1/128.  Embeddings are multiplied
by 12 and logits divided by 16; RMSNorm eps 1e-5.
"""

import dataclasses

from repro.configs.base import ModelConfig

#: ``layer_types`` of the published config: attention at 5, 15, 25, 35
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid_moe",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    n_experts=72,
    top_k=10,
    shared_expert_ff=1536,
    ssm_state=128,
    ssm_heads=128,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_expand=2,
    ssm_chunk=256,
    layer_types=LAYER_TYPES,
    position_embedding="nope",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
    norm_eps=1e-5,
    tied_embeddings=True,
    remat=False,
)

#: same family at tiny widths: both layer kinds in one period of 4, 8 experts
#: with 2 per token
SMOKE = dataclasses.replace(
    CONFIG, n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=32, vocab_size=256, n_experts=8, top_k=2, shared_expert_ff=48,
    ssm_state=16, ssm_heads=8, ssm_head_dim=16, ssm_chunk=8,
    layer_types=("mamba", "attention", "mamba", "mamba"),
    attention_multiplier=0.0625,
)
