"""deepseek-v2-lite-16b — MLA attention + token-choice MoE.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]  27 layers,
d_model 2048, 16 heads; MLA with kv_lora_rank 512, no q_lora, qk dims
128 (nope) + 64 (rope), v 128; YaRN rotary scaling (factor 40 over 4096
positions, mscale = mscale_all_dim = 0.707); layer 0 a dense SwiGLU of
10,944, layers 1-26 64 routed experts of 1,408 (top-6 of a softmax, not
renormalised, routed_scaling_factor 1) plus 2 shared; a sequence-level
balance loss (``seq_aux``, aux_loss_alpha 0.001) and no router z-loss;
RMSNorm at eps 1e-6; an untied 102,400-row vocabulary.
"""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YaRNConfig

CONFIG = ArchConfig(
    arch_id="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=0,
    vocab_size=102400,
    head_dim=128,                # v head dim; qk dims come from MLAConfig
    norm_eps=1e-6,
    rope_scaling=YaRNConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0,
                            mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(
        n_routed=64,
        n_shared=2,
        top_k=6,
        d_ff=1408,
        router_z_coef=0.0,
        aux_coef=0.001,
        seq_aux=True,
        first_dense_layers=1,
        dense_d_ff=10944,
    ),
    mla=MLAConfig(
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
    ),
    sub_quadratic=False,
    source="arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite",
)
