"""deepseek-v2-lite-16b [moe] — MLA (kv_lora=512, YaRN x40), 2 shared + 64
routed top-6 (softmax, not renormalised, sequence-wise aux 0.001).
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,             # dense MLP of the first (non-MoE) layer
    vocab_size=102400,
    mlp_type="swiglu",
    attention_type="mla",
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    d_ff_expert=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    moe_aux="seq",
    router_aux_coef=0.001,      # aux_loss_alpha
    rope_theta=10000.0,
    rope_scaling_factor=40.0,   # rope_scaling: yarn
    rope_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    source="arXiv:2405.04434",
    dp_mode="gossip",
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)
