"""moonlight-16b-a3b (Moonlight-16B-A3B, model_type deepseek_v3): 27
layers, the first dense (SwiGLU 11,264), then 26 MoE layers of 64 routed
SwiGLU experts of width 1,408, 6 per token, sigmoid scores with a
selection-only bias, normalised and scaled gates, and 2 shared experts;
latent attention (MLA) without a query low-rank, 16 heads.
[hf:moonshotai/Moonlight-16B-A3B/config.json]"""
from .base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv=16, head_dim=128,
    d_ff=11264, vocab=163840, unit=("moe",), act="swiglu",
    rope_theta=50000.0, norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    n_lead=1,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    score_func="sigmoid", routed_scale=2.446,
))
