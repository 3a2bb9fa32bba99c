"""Moonlight-16B-A3B  [moe, deepseek_v3] 27L d2048, MLA (16 heads, kv rank
512, qk 128 + 64 rope, v 128), the first layer dense (ff 11264), then 64
experts of 1408 top-6 with a sigmoid router, a selection bias and 2 shared
experts; V163840, context 8192.
[hf:moonshotai/Moonlight-16B-A3B]"""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(arch="moonlight-16b-a3b", family="moe", n_layers=27,
                       d_model=2048, n_heads=16, n_kv=16, head_dim=128,
                       d_ff=11264, vocab=163840, act="swiglu",
                       rope_theta=50000.0, n_experts=64, top_k=6,
                       capacity_factor=0.0, moe_d_ff=1408,
                       n_shared_experts=2, first_dense_layers=1,
                       router="sigmoid_bias", routed_scale=2.446,
                       norm_topk=True, aux_coef=1e-4, kv_lora_rank=512,
                       qk_rope_dim=64, v_head_dim=128)


def smoke_config() -> ModelConfig:
    return ModelConfig(arch="moonlight-smoke", family="moe", n_layers=3,
                       d_model=64, n_heads=4, n_kv=4, head_dim=16, d_ff=128,
                       vocab=257, act="swiglu", rope_theta=50000.0,
                       n_experts=8, top_k=2, capacity_factor=0.0,
                       moe_d_ff=32, n_shared_experts=2, first_dense_layers=1,
                       router="sigmoid_bias", routed_scale=2.446,
                       norm_topk=True, aux_coef=1e-4, kv_lora_rank=32,
                       qk_rope_dim=8, v_head_dim=16)
