"""A small DeepSeek-V3-family cell for the CPU tests: the configuration
file's keys at smoke widths (a dense layer 0 and two expert layers; 2 of 8
experts held, from expert 2 on), float32 compute."""

from __future__ import annotations

TINY_MOE = {
    "name": "tiny_moe", "program_arch": "moonlight_16b_a3b",
    "first_k_dense_replace": 1, "num_hidden_layers": 3, "hidden_size": 64,
    "intermediate_size": 128, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "moe_intermediate_size": 32,
    "n_routed_experts": 2, "router_width": 8, "expert_offset": 2,
    "n_shared_experts": 1, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "rms_norm_eps": 1e-5, "rope_theta": 50000, "vocab_size": 256,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "compute_dtype": "float32", "weights_dtype": "float32",
}
TINY_MOE_SERVE = {"driver": "serve_mla_moe", "batch": 4, "prompt_len": 8,
                  "gen_len": 4, "check_requests": 4}
