"""mellum2-12b-a2.5b: 64-expert top-8 MoE in every layer; three sliding-window
layers to one full-attention layer, YaRN RoPE on the full ones.
[hf JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
from repro.configs.base import ArchConfig, Yarn, register

ARCH = register(
    ArchConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
        num_layers=28,
        d_model=2304,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        d_ff=896,  # per-expert (moe_intermediate_size)
        vocab_size=98_304,
        mixer="attention",
        mlp_act="swiglu",
        norm="rmsnorm",
        rope_theta=500_000.0,
        num_experts=64,
        top_k=8,
        layer_pattern=("local", "local", "local", "attention"),
        local_window=1024,
        global_yarn=Yarn(factor=16.0, original_max_position_embeddings=8192,
                         beta_fast=32.0, beta_slow=1.0,
                         attention_factor=1.2772588722239782),
    )
)
