"""Moonlight-16B-A3B [moe] (``model_type: deepseek_v3``), as published in
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json:
27 layers at d_model=2048, layer 0 dense (FFN 11264,
``first_k_dense_replace: 1``), layers 1-26 mixture of experts. Multi-head
latent attention, 16 heads: no query compression (``q_lora_rank: null``),
a 512-wide KV latent with an RMS norm, 128 no-rope and 64 rope dims per
query-key head (the rope key shared by all heads), 128-wide values,
rope_theta 50000 with no scaling. 64 routed experts of width 1408, 6 per
token, plus 2 shared experts (width 2816); a sigmoid router selecting by
score plus a correction bias (``noaux_tc``, one group), weighting by the
unbiased scores normalised over the 6 and scaled by 2.446. Vocabulary
163840, untied; rms_norm_eps 1e-5."""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,            # v_head_dim: the width wo reads per head
    d_ff=11264,              # the dense layer 0's FFN
    vocab_size=163840,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    router="sigmoid",
    routed_scaling=2.446,
    n_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    act="silu",
    notes="Pure full (latent) attention: long_500k skipped.",
)
