"""Mellum2-12B-A2.5B [hf:JetBrains/Mellum2-12B-A2.5B-Instruct].

JetBrains' code MoE decoder: 28 layers of 3 sliding-window (w=1024)
layers then 1 full-attention layer, GQA 32Q/4KV of 128, every MLP sparse:
64 routed SwiGLU experts of width 896, top-8 with the top-8 gates
renormalised, no shared expert.  RoPE by layer type: default θ 5e5 on the
sliding layers; YaRN (factor 16 over 8,192 positions, β 32/1, attention
factor 1.2773) on the full ones.  RMSNorm eps 1e-6, untied embeddings,
vocabulary 98,304.  The config names no router auxiliary loss.

Each layer holds 8 of its 64 experts: a chip's share when 8 chips split
every layer's experts (``n_experts_held``); the router keeps its 64
outputs and the layer runs dropless over the held share.
"""
from repro.configs.base import ModelConfig, YarnRope

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,
    vocab_size=98304,
    use_rope=True,
    rope_theta=500_000.0,
    rope_yarn=YarnRope(factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0,
                       beta_slow=1.0, attention_factor=1.2772588722239782),
    sliding_window=1024,
    local_global_pattern=(3, 1),
    mlp_type="gated_silu",
    n_experts=64,
    n_experts_held=8,
    top_k=8,
    capacity_factor=None,
    router_z_loss=0.0,
    load_balance_loss=0.0,
    dtype="bfloat16",
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)
