"""deepseek-v2-lite-16b — MLA + fine-grained MoE [arXiv:2405.04434].

As published (huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json):
27 layers at d=2048, 16 heads of MLA with no q LoRA (kv_lora_rank 512,
q/k head dim 128 + 64 RoPE, v head dim 128, YaRN RoPE with factor 40 over
an original 4096 positions, mscale 0.707 on all dims).  Layer 0 has a
dense SwiGLU MLP of width 10944 (``first_k_dense_replace`` 1); the other
26 route each token to 6 of 64 experts of width 1408 (softmax scores,
greedy top-k, weights not renormalised) beside 2 shared experts.  The
experts run dropless: no token is dropped for capacity.
"""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    rope_theta=1e4,
    mlp_act="silu",
    norm_eps=1e-6,
    stack_pattern=(("mla_dense", 1), ("mla_moe", 26)),
    mla=MLAConfig(
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_factor=40.0,
        rope_original_max_position=4096,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=0.707,
        rope_mscale_all_dim=0.707,
    ),
    moe=MoEConfig(
        num_experts=64, top_k=6, d_ff_expert=1408, num_shared=2,
        norm_topk_prob=False, dispatch="dropless",
    ),
    source="arXiv:2405.04434",
)
