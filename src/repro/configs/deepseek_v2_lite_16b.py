"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H, MLA kv_lora=512,
MoE: 2 shared + 64 routed experts top-6, expert d_ff=1408, vocab=102400,
first layer dense (d_ff=10944); softmax router, top-6 weights not
renormalized; YaRN rope scaling (factor 40 over 4096 positions).
[arXiv:2405.04434; hf]"""

from repro.configs import base
from repro.models.config import ModelConfig, YaRN

# config.json's rope_scaling (type "yarn")
ROPE_SCALING = YaRN(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707)


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b", family="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=1408, vocab_size=102400,
        mla=True, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128, moe=True, n_experts=64, top_k=6, moe_d_ff=1408,
        n_shared_experts=2, first_dense_layers=1, dense_d_ff=10944,
        capacity_factor=1.25, rope_theta=10000.0, rope_scaling=ROPE_SCALING,
        norm_topk_prob=False, norm_eps=1e-6)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-smoke", family="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=256, mla=True,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        moe=True, n_experts=8, top_k=2, moe_d_ff=96, n_shared_experts=2,
        first_dense_layers=1, dense_d_ff=128, capacity_factor=2.0,
        rope_theta=10000.0, rope_scaling=ROPE_SCALING, norm_topk_prob=False,
        norm_eps=1e-6, remat=False)


base.register("deepseek-v2-lite-16b", full, smoke)
