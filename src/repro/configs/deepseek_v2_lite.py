"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434] —
multi-head latent attention (§2.1) and DeepSeekMoE (§2.2).

27L d_model=2048 16 heads, no q-LoRA: q 2048 -> 16x(128 + 64 rotated);
kv latent 512 + one shared 64-dim rotated key, RMSNorm on the latent, then
512 -> 16x(128 key + 128 value); YaRN (factor 40 over 4096 positions);
layer 0 a dense SwiGLU of 10944, layers 1-26 64 routed experts of 1408,
top-6 softmax, no renormalisation, plus 2 shared experts (one SwiGLU of
2816); vocabulary 102400, untied; RMSNorm epsilon 1e-6.

``CONFIG`` is one chip's share of the stated deployment, a v5e-4 host with
experts expert-parallel over its 4 chips: this chip holds experts 0-15 of
every MoE layer, everything else whole (data-parallel attention).  Rotary
pairs are the published interleaved ones (``layers.apply_rope_interleaved``).
"""
from repro.config import ModelConfig, MoEConfig, reduced

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab_size=102400,
    rope_theta=10_000.0,
    rope_scaling={"factor": 40, "original_max_position_embeddings": 4096,
                  "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                  "mscale_all_dim": 0.707, "type": "yarn"},
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe=MoEConfig(num_experts=64, top_k=6, expert_ff=1408, first_dense=1,
                  shared_ff=2816, norm_topk=False, experts_held=16),
)
SMOKE = reduced(CONFIG)
