"""deepseek-v2-lite-16b [moe] — 27L d_model=2048 16H, MLA (no q_lora,
kv_lora 512 with an RMSNorm on the latent, qk_nope 128, decoupled shared
qk_rope 64, v 128), YaRN RoPE (factor 40 over 4096 original positions),
one leading dense SiLU-GLU layer of width 10944, then MoE layers of 64
routed experts of width 1408 (softmax scores, greedy top-6, gates not
renormalised) and 2 shared experts; RMSNorm eps 1e-6, untied head,
vocab 102400. Values as the published config.json states them
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434).
"""

from repro.configs.base import ModelConfig, YarnScaling

config = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,              # intermediate_size: the leading dense layer
    vocab_size=102400,
    moe=True,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    d_ff_expert=1408,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    first_dense=1,
    attn_type="mla",
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    head_dim=192,            # qk_nope + qk_rope
    rope_variant="yarn",
    rope_scaling=YarnScaling(
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    norm_eps=1e-6,
    source="arXiv:2405.04434",
)
