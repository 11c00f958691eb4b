"""deepseek-v2-lite-16b: MoE, 27L d_model=2048 16H d_ff=1408(expert) vocab=102400.

MLA attention (kv_lora_rank=512, no q compression in Lite) with YaRN rotary
(factor 40 over 4096 positions), 64 routed experts top-6 with unnormalised
softmax gates + 2 shared experts, first layer dense (d_ff=10944), RMSNorm
eps 1e-6 (deepseek-ai/DeepSeek-V2-Lite config.json).
[arXiv:2405.04434; hf]  Note: the "160 routed" figure belongs to full V2; the
assignment line specifies "MoE 64e top-6" which matches V2-Lite, used here.
"""
from repro.configs.base import (AttentionConfig, ModelConfig, MoEConfig,
                                YarnConfig)

ARCH_ID = "deepseek-v2-lite-16b"

YARN = YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707)


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        family="moe",
        num_layers=27,
        d_model=2048,
        d_ff=10944,                    # dense FFN width for first_dense_layers
        vocab_size=102400,
        attention=AttentionConfig(
            kind="mla",
            num_heads=16,
            num_kv_heads=16,
            head_dim=192,              # qk_nope + qk_rope
            kv_lora_rank=512,
            q_lora_rank=0,             # Lite: direct q projection
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            rope_theta=10000.0,
            yarn=YARN,
        ),
        moe=MoEConfig(
            num_experts=64,
            top_k=6,
            expert_ff=1408,
            num_shared=2,
            shared_ff=1408,
            first_dense_layers=1,
            transport="local",
            norm_topk_prob=False,
            routed_scaling_factor=1.0,
        ),
        norm_eps=1e-6,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke",
        family="moe",
        num_layers=3,
        d_model=64,
        d_ff=160,
        vocab_size=256,
        attention=AttentionConfig(
            kind="mla", num_heads=4, num_kv_heads=4, head_dim=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, yarn=YARN,
        ),
        moe=MoEConfig(
            num_experts=8, top_k=2, expert_ff=32, num_shared=2, shared_ff=32,
            first_dense_layers=1, transport="local", norm_topk_prob=False,
        ),
        norm_eps=1e-6,
        remat="none",
    )
