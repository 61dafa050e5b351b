"""granite-4.0-h-small (32B-A9B) as one chip's share of its deployment.

Published: 40 layers in four periods of ``MMMMMAMMMM`` (Mamba2 mixers, and
NoPE GQA attention at 5, 15, 25, 35), d_model 4096; Mamba2 128 heads of 64,
state 128, one group, conv 4, expand 2, chunk 256; attention 32 query and 8
KV heads of 128, scores scaled by 1/128; after every mixer a router over 72
SwiGLU experts of width 768 (top-10, softmax over the top-10 logits) and a
shared SwiGLU of width 1536; embeddings x12, each residual branch x0.22,
logits / 16; a tied vocabulary of 100,352 rows; RMS norm eps 1e-5.
[hf:ibm-granite/granite-4.0-h-small]

The share served here: 8 chips divide each FFN layer's experts, 9 to a
chip, and this one holds experts 0-8; the layers form 2 pipeline stages,
and this chip is one of them, 2 periods (20 layers: 18 Mamba2, 2
attention). The router, the mixers, the shared expert, the vocabulary and
every width are whole.
"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid_moe",
    num_layers=20,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=768,
    moe_d_ff=768,
    vocab_size=100352,
    layer_kinds=("mamba",) * 5 + ("attn_global",) + ("mamba",) * 4,
    position_embedding="nope",
    attn_scale=0.0078125,
    num_experts=72,
    num_experts_per_tok=10,
    moe_experts_held=9,
    moe_expert_offset=0,
    pipeline_stages=2,
    shared_d_ff=1536,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv_width=4,
    ssm_chunk=256,
    ssm_groups=1,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    max_seq_len=131072,
)
