"""A tiny latent-attention MoE configuration in the benchmark's file
format, for the CPU tests of the program against the reference."""

from __future__ import annotations

TINY_MLA = {
    "name": "tiny-mla", "source": "test", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 24,
    "n_routed_experts": 4, "num_experts_per_tok": 3, "n_shared_experts": 2,
    "norm_topk_prob": False, "routed_scaling_factor": 1, "vocab_size": 256,
    "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "tie_word_embeddings": False, "attention_bias": False,
    "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 16},
    "arch": "deepseek-v2-lite",
    "program": {"name": "tiny-mla", "n_layers": 3, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 96,
                "vocab_size": 256, "kv_lora_rank": 32,
                "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                "v_head_dim": 16, "n_experts": 16, "top_k": 3,
                "d_ff_expert": 24, "expert_shard": [1, 4]},
}


def program_config(c):
    from bench import model_config
    return model_config.program_config(c)


def config_pair(c=TINY_MLA):
    """(program ModelConfig, reference MLAShape) of a tiny conf."""
    from bench.reference import deepseek_v2
    cfg = program_config(c)
    assert cfg.n_held_experts == c["n_routed_experts"]
    return cfg, deepseek_v2.shape_of(c)
