"""mellum2-12b-a2.5b [hf:JetBrains/Mellum2-12B-A2.5B-Instruct; hf] — code
model: 64-expert top-8 MoE in every layer, sliding-window (1024) and full
attention 3:1, YaRN on the full layers."""
from .base import ArchConfig, Rope

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=7168,               # intermediate_size: no dense layer uses it
    vocab_size=98304,
    rope_theta=5e5,
    norm_eps=1e-6,
    moe_num_experts=64,
    moe_top_k=8,
    moe_every=1,             # mlp_layer_types: every layer sparse
    moe_d_ff=896,            # moe_intermediate_size
    layer_types=("sliding", "sliding", "sliding", "full"),
    sliding_window=1024,
    rope_by_type=(
        ("full", Rope(theta=5e5, yarn_factor=16.0,
                      yarn_original_max_pos=8192, yarn_beta_fast=32.0,
                      yarn_beta_slow=1.0,
                      attention_factor=1.2772588722239782)),
        ("sliding", Rope(theta=5e5)),
    ),
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct; hf",
)
