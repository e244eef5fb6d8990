"""DeepSeek-V3.2 (the paper's own model) — MLA + DeepSeek Sparse Attention.

As published (huggingface.co/deepseek-ai/DeepSeek-V3.2, config.json): 61
layers, the first 3 dense (MLP width 18432), the other 58 expert layers of
256 routed experts (width 2048, 8 a token) and 1 shared expert, routed by
DeepSeek's ``noaux_tc`` router (sigmoid scores, 8 groups of which a token
may use 4, normalised weights times 2.5); MLA with a 512 + 64 latent, YaRN
RoPE (factor 40 over 4096 original positions); the V3.2 lightning indexer
of 64 heads of 128 (64 of them rope dims), query from the query latent,
top-k 2048.  ``n_experts_held`` holds all 256; a chip's share of an
expert-parallel deployment sets it (chipbench/configs/deepseek-v32.json).
"""
from repro.configs.base import ModelConfig, SACConfig

CONFIG = ModelConfig(
    name="deepseek-v32", family="mla",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, d_ff=2048,
    vocab=129280, head_dim=128, rope_theta=10000.0,
    mla=True, kv_lora_rank=512, qk_rope_dim=64, q_lora_rank=1536,
    idx_rope_dim=64,
    rope_factor=40.0, rope_original_max_pos=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
    n_experts=256, topk_experts=8, first_k_dense=3, d_ff_dense=18432,
    n_shared_experts=1, router="noaux_tc", n_expert_groups=8,
    n_limited_groups=4,
    norm_topk_prob=True, routed_scaling_factor=2.5,
    sac=SACConfig(enabled=True, topk=2048, d_idx=128, n_idx_heads=64,
                  device_buffer_size=6144),
)
