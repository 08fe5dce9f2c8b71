"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H, MLA kv_lora=512.

MoE: 64 routed top-6 (softmax scores, not renormalised) + 2 shared, expert
d_ff=1408; the first layer dense at d_ff=10944.  Latent ``c`` RMS-normed,
YaRN rotary (factor 40 over 4096 original positions).  vocab=102400.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]
"""
from repro.models.common import MLAConfig, ModelConfig, MoEConfig, YarnConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b", family="moe",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        head_dim=128, d_ff=10944, vocab_size=102_400,
        mla=MLAConfig(kv_lora_rank=512, qk_rope_dim=64, qk_nope_dim=128,
                      v_head_dim=128),
        yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                        beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        moe=MoEConfig(num_experts=64, top_k=6, num_shared=2,
                      expert_d_ff=1408, first_dense=1, norm_topk_prob=False,
                      routed_scaling_factor=1.0),
    )


def smoke_config() -> ModelConfig:
    import jax.numpy as jnp
    return config().replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
        d_ff=192, vocab_size=256,
        mla=MLAConfig(kv_lora_rank=32, qk_rope_dim=8, qk_nope_dim=16,
                      v_head_dim=16),
        moe=MoEConfig(num_experts=8, top_k=2, num_shared=1,
                      expert_d_ff=64, first_dense=1, norm_topk_prob=False),
        moe_impl="dense", compute_dtype=jnp.float32,
    )
