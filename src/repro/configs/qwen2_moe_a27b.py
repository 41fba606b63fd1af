"""qwen2-moe-a2.7b — token-choice MoE, 60 routed top-4 + 4 shared experts.

[hf:Qwen/Qwen1.5-MoE-A2.7B; hf]  24L, d_model=2048, 16H (kv=16), expert
d_ff=1408, vocab=151936.  Routed experts padded 60 -> 64 so the expert axis
divides the 16-way model mesh axis (the router masks the pad experts out).
"""
from repro.configs.base import ArchConfig, MoEConfig

CONFIG = ArchConfig(
    arch_id="qwen2-moe-a2.7b",
    family="moe",
    n_layers=24,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=0,                      # all FFN capacity is MoE
    vocab_size=151936,
    qkv_bias=True,
    moe=MoEConfig(
        n_routed=60,
        n_shared=4,
        top_k=4,
        d_ff=1408,
        n_padded=64,
    ),
    sub_quadratic=False,
    source="hf:Qwen/Qwen1.5-MoE-A2.7B; hf",
)
