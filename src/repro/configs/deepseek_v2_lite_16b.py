"""DeepSeek-V2-Lite (15.7B total / 2.4B active) [arXiv:2405.04434].

The published config (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/
blob/main/config.json): MLA attention (kv_lora_rank 512, no q compression,
decoupled rope head 64), one leading dense layer (intermediate 10944), then
26 MoE layers of 64 routed experts (1408 wide, softmax gate, greedy top-6)
and 2 shared experts. ``norm_topk_prob`` false: the top-6 weights are the
softmax probabilities as they are, times ``routed_scaling_factor`` 1.
``rope_scaling``: yarn, factor 40, beta_fast 32, beta_slow 1, mscale and
mscale_all_dim 0.707, original_max_position_embeddings 4096.

Registered whole (every chip holds all 64 experts); a deployment that
spreads the experts over chips sets ``n_experts_held``/``expert_offset``.
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,                 # MoE expert intermediate size
    vocab_size=102400,
    # MLA
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,             # V2-Lite has no q compression
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    head_dim=192,              # qk_nope + qk_rope
    # MoE
    n_experts=64,
    experts_per_tok=6,
    n_shared_experts=2,
    d_ff_expert=1408,
    first_dense_layers=1,
    d_ff_dense=10944,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    # rope: yarn
    rope_theta=1e4,
    rope_kind="yarn",
    rope_factor=40.0,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_original_max_pos=4096,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
))
