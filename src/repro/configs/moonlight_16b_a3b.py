"""moonlight-16b-a3b [mla_moe]: 27L d_model=2048, MLA 16H (kv_lora_rank
512, qk 128 nope + 64 rope, v 128), layer 0 dense FFN 11264, then 26 MoE
layers of 64 routed experts (width 1408, top-6, sigmoid noaux_tc routing,
scaling 2.446) + 2 shared; vocab 163840, rope_theta 50000, untied head.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

``ep8_share`` is one chip's share of an EP8 deployment (the routed experts
of each MoE layer over 8 chips, attention and dense layers replicated, the
vocabulary split 8 ways) at the depth one chip holds: layer 0 and 4 MoE
layers, 8 of 64 experts, 20480 of the 163840 ids.  Every width is as
published.
"""
import dataclasses

from ..models.mla_moe import MLAMoEConfig

ARCH_ID = "moonlight-16b-a3b"


def config() -> MLAMoEConfig:
    return MLAMoEConfig(
        vocab_size=163840, hidden_size=2048, num_hidden_layers=27,
        first_k_dense_replace=1, intermediate_size=11264,
        num_attention_heads=16, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=64,
        num_experts_per_tok=6, moe_intermediate_size=1408,
        n_shared_experts=2, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
        rope_theta=50000.0, max_position_embeddings=8192, experts_held=64)


def ep8_share() -> MLAMoEConfig:
    return dataclasses.replace(config(), num_hidden_layers=5,
                               experts_held=8, vocab_size=20480)


def smoke_config() -> MLAMoEConfig:
    return MLAMoEConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=48,
        num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, n_routed_experts=32,
        num_experts_per_tok=4, moe_intermediate_size=12,
        n_shared_experts=2, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
        rope_theta=50000.0, max_position_embeddings=64, experts_held=4)
