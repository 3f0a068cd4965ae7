"""Moonlight-16B-A3B (moonshotai) — the DeepSeek-V3 block: latent attention
and sparse experts. [hf:moonshotai/Moonlight-16B-A3B config.json]
27L d_model=2048 16H; MLA without q-LoRA (kv_lora_rank 512, qk 128 nope +
64 rope, v 128); layer 0 dense SwiGLU 11264 wide, layers 1-26 MoE: 64
routed SwiGLU experts of width 1408 (sigmoid scores, top 6 by score plus
selection bias, weights renormalised and scaled by 2.446) plus 2 shared
experts; vocab 163840 untied; RMSNorm eps 1e-5; RoPE theta 50000.

RoPE pairs the two halves of the rope dimensions (the repo's form);
DeepSeek's checkpoints pair interleaved dimensions. With seeded weights the
two differ only by a permutation of the rope columns of `wq` and `wkv_a`.
"""
from repro.config.base import ModelConfig

ARCH_ID = "moonlight-16b-a3b"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="moe",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        head_dim=192, d_ff=1408, vocab_size=163840,
        num_experts=64, experts_per_token=6, shared_experts=2,
        router_score="sigmoid", router_scale=2.446, router_bias_rate=1e-3,
        router_aux_weight=1e-4, first_dense_layers=1, dense_d_ff=11264,
        kv_lora_rank=512, qk_rope_dim=64, v_head_dim=128,
        norm_type="rmsnorm", norm_eps=1e-5, mlp_act="swiglu",
        rope_theta=50_000.0,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="moe",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=24, d_ff=32, vocab_size=256,
        num_experts=8, experts_per_token=3, shared_experts=2,
        router_score="sigmoid", router_scale=2.446, router_bias_rate=1e-3,
        router_aux_weight=1e-4, first_dense_layers=1, dense_d_ff=96,
        kv_lora_rank=32, qk_rope_dim=8, v_head_dim=16,
        norm_type="rmsnorm", norm_eps=1e-5, mlp_act="swiglu",
        rope_theta=50_000.0,
    )
