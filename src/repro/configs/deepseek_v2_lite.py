"""DeepSeek-V2-Lite — MoE with MLA and YaRN
[hf:deepseek-ai/DeepSeek-V2-Lite config.json].

27L, d_model=2048, 16 heads, MLA kv_lora=512 with no q LoRA (qk_nope=128,
qk_rope=64, v=128), YaRN (factor 40 over 4096 positions, beta 32/1,
mscale = mscale_all_dim = 0.707), 64 routed experts top-6 (greedy over
softmax, not renormalised, scaling 1) + 2 shared, per-expert d_ff=1408,
first layer dense (d_ff=10944), sequence-wise aux loss, vocab 102400,
untied head, RMSNorm eps 1e-6. ``aux_loss_alpha``
is not in the published config; 0.001 is the family's value.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,           # MLA: kv heads == heads post up-projection
    d_ff=10944,                # dense (first) layer hidden
    vocab_size=102400,
    attn_type="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    head_dim=128,
    rope_theta=1e4,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    router_aux="seq",
    router_aux_coef=0.001,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    norm_eps=1e-6,
)
