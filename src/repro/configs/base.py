"""Architecture & shape configuration system.

Every assigned architecture gets one module in ``repro.configs`` exporting a
``CONFIG: ArchConfig``.  The registry (``repro.configs.registry``) resolves
``--arch <id>`` strings to configs and model implementations.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed: int = 0            # number of routed experts (as published)
    n_shared: int = 0            # shared (always-on) experts
    top_k: int = 2
    d_ff: int = 0                # per-expert hidden dim
    n_padded: int = 0            # routed experts padded for EP divisibility
    # the experts whose weights this model holds: ``n_held`` of them from
    # index ``first_held`` (0: all).  The router still scores every
    # expert; pairs routed to an expert held elsewhere add nothing here
    # (one chip's share of an expert-parallel layer), and the router
    # learns from the balance loss alone.
    n_held: int = 0
    first_held: int = 0
    router_z_coef: float = 1e-3
    aux_coef: float = 1e-2       # expert balance loss
    seq_aux: bool = False        # balance loss per sequence (DeepSeek-V2)
    first_dense_layers: int = 0  # leading layers that use a dense FFN instead
    dense_d_ff: int = 0          # hidden dim of those dense layers

    @property
    def n_experts_padded(self) -> int:
        return self.n_padded or self.n_routed

    @property
    def n_experts_held(self) -> int:
        return self.n_held or self.n_experts_padded


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YaRNConfig:
    """YaRN rotary scaling, in the keys of a HF ``rope_scaling`` entry."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    family: str                  # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0            # 0 -> d_model // n_heads
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0   # partial RoPE (stablelm = 0.25)
    rope_scaling: Optional[YaRNConfig] = None
    use_rope: bool = True
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    norm_eps: float = 1e-5
    parallel_block: bool = False # command-r style attn || ffn
    qkv_bias: bool = False
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    act: str = "silu"            # silu (SwiGLU) | gelu

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None

    # --- hybrid (zamba2): mamba backbone + shared attention block -----------
    hybrid_attn_every: int = 0   # apply the shared attn block every N ssm blocks

    # --- xlstm: block pattern --------------------------------------------
    slstm_every: int = 0         # every Nth block is an sLSTM (rest mLSTM)

    # --- encoder-decoder (whisper) ---------------------------------------
    is_encdec: bool = False
    n_enc_layers: int = 0
    enc_seq_len: int = 0         # stub frontend sequence length (audio frames)

    # --- vlm (llama-3.2-vision) -------------------------------------------
    cross_every: int = 0         # every Nth layer is a cross-attention layer
    n_media_tokens: int = 0      # stub patch-embedding token count

    # frontend stub: None | 'audio' | 'patch'
    frontend: Optional[str] = None

    # sub-quadratic? (eligible for long_500k)
    sub_quadratic: bool = False

    max_seq: int = 532_480
    source: str = ""

    def __post_init__(self):
        # nested groups given as dicts (a config read from JSON) become
        # their dataclasses, so the config stays hashable
        for name, cls in (("moe", MoEConfig), ("mla", MLAConfig),
                          ("ssm", SSMConfig), ("rope_scaling", YaRNConfig)):
            value = getattr(self, name)
            if isinstance(value, dict):
                object.__setattr__(self, name, cls(**value))

    # ---------------------------------------------------------------- utils
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def param_count(self) -> int:
        """Analytic parameter count (matches init to within ties/pads)."""
        from repro.models.registry import param_count  # lazy: avoids cycle
        return param_count(self)

    def active_param_count(self) -> int:
        from repro.models.registry import active_param_count
        return active_param_count(self)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def tokens(self) -> int:
        if self.kind == "decode":
            return self.global_batch          # one new token per sequence
        return self.seq_len * self.global_batch


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", "train", 4_096, 256),
    ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    ShapeConfig("decode_32k", "decode", 32_768, 128),
    ShapeConfig("long_500k", "decode", 524_288, 1),
)

SHAPES_BY_NAME = {s.name: s for s in SHAPES}


def shape_applicable(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether a (arch x shape) cell runs; returns (ok, reason_if_skipped)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("pure full-attention arch: 524k-token dense-attention "
                       "decode is the quadratic regime long_500k excludes")
    return True, ""
