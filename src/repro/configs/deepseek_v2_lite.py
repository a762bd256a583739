"""deepseek-v2-lite — MLA (kv_lora_rank 512, no q-LoRA) with YaRN rotary,
one leading dense layer, then 26 MoE layers of 64 routed experts (top-6,
softmax gate, no renormalisation) plus 2 shared
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]."""
from .base import ModelConfig, Yarn, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=10944, vocab_size=102400, rope_theta=1e4,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, n_shared_experts=2, d_ff_expert=1408,
    norm_topk_prob=False, first_k_dense=1,
    source="hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434",
))
