"""Mellum2-12B-A2.5B — sparse-expert MoE (64 experts, top-8, dropless) on
every layer; 3 sliding-window layers (1024) then 1 full layer, YaRN RoPE
on the full ones [hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]."""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,                       # moe_intermediate_size: the expert width
    vocab_size=98304,
    num_experts=64,
    experts_per_token=8,            # softmax over 64, top 8, renormalised
    capacity_factor=0.0,            # dropless
    sliding_window=1024,
    global_every=4,                 # 3 sliding : 1 full
    rope_theta=5e5,
    yarn_factor=16.0,               # attention factor 0.1 ln 16 + 1
    yarn_original_max=8192,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
))
