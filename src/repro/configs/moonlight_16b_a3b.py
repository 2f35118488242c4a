"""moonlight-16b-a3b [moe]: 27L d_model=2048 16H, multi-head latent attention
(kv_lora_rank 512, qk_nope 128, qk_rope 64, v 128, no q compression), one
leading dense layer of width 11264, then MoE layers of 64 routed experts
(width 1408, sigmoid scores, top-6 on score + bias, renormalised, x2.446)
beside 2 shared experts, vocab=163840, RoPE theta 50000 over adjacent pairs.
The DeepSeek-V3 block.  Routed without capacity, so the capacity factor is
num_experts / experts_per_token: no assignment is ever dropped.
[hf:moonshotai/Moonlight-16B-A3B config.json; model_type deepseek_v3]"""
from repro.configs.base import ArchConfig
from repro.configs.registry import reduce_common

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=1408, vocab_size=163840, head_dim=128,
    num_experts=64, experts_per_token=6, capacity_factor=64 / 6,
    dense_residual=True, residual_d_ff=2 * 1408,
    router_score="sigmoid", router_scale=2.446,
    first_dense_layers=1, dense_d_ff=11264,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=50000.0,
)


def reduced():
    # 1 dense + 2 MoE layers; 8 experts top-2 keep routing choices real
    return reduce_common(
        CONFIG, num_layers=3, num_experts=8, experts_per_token=2,
        capacity_factor=8 / 2, residual_d_ff=64, dense_d_ff=96,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16)
