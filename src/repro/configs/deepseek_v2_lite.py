"""DeepSeek-V2-Lite 15.7B [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H MLA without q-LoRA (kv_lora=512, qk_nope=128,
qk_rope=64, v=128) vocab=102400, untied head.  MoE: 2 shared + 64 routed
experts, top-6 from a softmax without renormalisation, expert_d_ff=1408;
first layer dense d_ff=10944.  YaRN RoPE (factor 40 over 4096 positions,
mscale = mscale_all_dim = 0.707), rope_theta 10000.
"""
from repro.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,    # MLA: all heads share the compressed kv cache
    head_dim=128,
    d_ff=10_944,        # the dense first layer
    vocab_size=102_400,
    activation="swiglu",
    position="rope",
    rope_theta=10_000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                  num_shared_experts=2, shared_d_ff=2 * 1408,
                  first_k_dense=1, dense_d_ff=10_944,
                  norm_topk_prob=False),
)
