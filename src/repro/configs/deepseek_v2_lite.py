"""deepseek-v2-lite [moe] — arXiv:2405.04434,
hf:deepseek-ai/DeepSeek-V2-Lite (config.json).

27L d_model=2048 16H, multi-head latent attention (kv_lora_rank 512,
qk 128 + 64 rope, v 128, no q-LoRA), YaRN rope (factor 40 over 4,096,
beta 32/1, mscale 0.707), vocab 102400 untied.  Layer 0 is a dense
SwiGLU of 10,944; every later layer is an MoE of 64 routed experts of
1,408 (top-6, softmax, unnormalised gates) plus 2 shared experts.
"""
from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                YarnConfig, replace)

ARCH_ID = "deepseek-v2-lite"

FULL = ModelConfig(
    name=ARCH_ID,
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=102400,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    moe=MoEConfig(
        num_experts=64, top_k=6, num_shared_experts=2, d_expert=1408,
        first_k_dense=1, dense_d_ff=10944, norm_topk_prob=False,
    ),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
)

SMOKE = replace(
    FULL, name=ARCH_ID + "-smoke",
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=48,
    vocab_size=256,
    moe=MoEConfig(num_experts=8, top_k=3, num_shared_experts=1, d_expert=48,
                  first_k_dense=1, dense_d_ff=128, norm_topk_prob=False),
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    yarn=YarnConfig(factor=4.0, original_max_position=16),
)
