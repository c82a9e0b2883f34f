"""Kimi-VL-A3B-Instruct, its language model [hf:moonshotai/Kimi-VL-A3B-Instruct].

27L, d_model=2048, latent attention (16 heads, kv_lora_rank 512, no q-LoRA,
qk 128 + 64 rotated, v 128, rope_theta 800000); layer 0 a dense SwiGLU of
11264; layers 1-26 64 routed SwiGLU experts of 1408, top-6 by noaux_tc
(sigmoid scores plus a correction bias, normalised, x 2.446), plus 2 shared
experts; vocab 163840, untied head, RMSNorm eps 1e-5. MoonViT and the
projector are not part of this entry: frames arrive as prompt tokens.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-vl-a3b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,  # qk_nope_head_dim + qk_rope_head_dim
    d_ff=1408,  # per routed (and shared) expert
    vocab_size=163840,
    rope_theta=800000.0,
    tie_embeddings=False,
    n_experts=64,
    moe_top_k=6,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    norm_eps=1e-5,
    first_k_dense=1,
    dense_d_ff=11264,
    moe_router_experts=64,
    moe_routed_scale=2.446,
    moe_n_shared=2,
    citation="hf:moonshotai/Kimi-VL-A3B-Instruct",
)
