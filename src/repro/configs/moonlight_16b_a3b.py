"""moonlight-16b-a3b — Moonshot's DeepSeek-V3-style MoE: latent attention
(MLA) in every layer, one leading dense layer, then 26 layers of 64
routed experts (top-6, sigmoid scores with a selection-only bias,
normalised and scaled) beside 2 shared experts.
[hf:moonshotai/Moonlight-16B-A3B config.json]"""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,                # moe_intermediate_size, per routed expert
    vocab_size=163840,
    rope_theta=50000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    experts_per_token=6,
    n_shared_experts=2,
    router_scoring="sigmoid",
    routed_scale=2.446,
    first_k_dense=1,
    dense_d_ff=11264,         # intermediate_size of the dense layer
    grad_accum=4,
    source="hf:moonshotai/Moonlight-16B-A3B",
)
