"""moonlight-16b-a3b [moe]: Moonlight-16B-A3B, DeepSeek-V3's block.

27L (1 dense SwiGLU of width 11264, then 26 MoE), d_model=2048, 16 heads
of latent attention (kv_lora_rank 512, q 128+64 per head, v 128, no q
compression), 64 routed experts of width 1408, top-6 by sigmoid score
plus a selection-only bias, gates normalized then scaled by 2.446, two
shared experts; vocab 163840, untied, rope_theta 50000, RMSNorm eps 1e-5.
Dropless inference dispatch.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=11264, vocab=163840,
    rope_theta=50000.0, rms_norm_eps=1e-5, tie_embeddings=False,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, first_k_dense=1, n_experts=64, top_k=6, moe_d_ff=1408,
    n_shared_experts=2, router="noaux_tc", routed_scaling_factor=2.446,
    moe_dropless=True)

SMOKE = CONFIG.replace(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96, vocab=256,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_experts=8, top_k=2, moe_d_ff=24, attn_impl="full", remat="none")
