"""Plain float32 reference of a dense pre-norm transformer language model
(StableLM 2 layout), its weights and its model FLOPs.

Written from the StableLM 2 model card and ``config.json``
(hf:stabilityai/stablelm-2-1_6b) and independent of the program's code:

* token embedding (V, D), an untied output head (D, V);
* each layer: LayerNorm -> multi-head attention with biased q, k, v
  projections, rotary position embedding on the first
  ``rope_fraction`` of each head's dimensions (rotate-half pairing),
  causal softmax -> output projection -> residual; LayerNorm -> SwiGLU
  MLP (``silu(x W_gate) * (x W_up)`` then ``W_down``) -> residual;
* a final LayerNorm and the head; the loss adds a z-loss of
  ``z_loss * logsumexp**2``.

Attention is the plain softmax over all earlier positions, computed in
blocks of query rows so that it fits.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from chipbench.models import common as C

Params = Dict[str, Any]


def _hd(cfg) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def _rope_dim(cfg) -> int:
    rd = int(_hd(cfg) * cfg["rope_fraction"])
    return rd - rd % 2


# ---------------------------------------------------------------- weights

def init(key, cfg) -> Params:
    """The benchmark's weights for ``cfg`` in the dtypes they are trained
    in.  Biases of q, k and v are drawn small and non-zero so that the
    forward pass uses them."""
    D, F, V, L = (cfg["d_model"], cfg["d_ff"], cfg["vocab_size"],
                  cfg["n_layers"])
    H, Kv, hd = cfg["n_heads"], cfg["n_kv_heads"], _hd(cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(key, 16))

    def dense(k, shape, d_in, scale=None):
        s = scale if scale is not None else 1.0 / math.sqrt(d_in)
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, f32)
                * s).astype(bf16)

    def ln(lead, d):
        return {"w": jnp.ones(lead + (d,), f32),
                "b": jnp.zeros(lead + (d,), f32)}

    def bias(k, n):
        return (jax.random.normal(k, (L, n), f32) * 0.02).astype(bf16)

    blocks = {
        "attn_norm": ln((L,), D),
        "attn": {
            "wq": dense(next(ks), (L, D, H * hd), D),
            "wk": dense(next(ks), (L, D, Kv * hd), D),
            "wv": dense(next(ks), (L, D, Kv * hd), D),
            "wo": dense(next(ks), (L, H * hd, D), H * hd),
            "bq": bias(next(ks), H * hd),
            "bk": bias(next(ks), Kv * hd),
            "bv": bias(next(ks), Kv * hd),
        },
        "ffn_norm": ln((L,), D),
        "ffn": {
            "w_up": dense(next(ks), (L, D, F), D),
            "w_down": dense(next(ks), (L, F, D), F),
            "w_gate": dense(next(ks), (L, D, F), D),
        },
    }
    return {
        "embed": dense(next(ks), (V, D), 1, scale=0.02),
        "final_norm": ln((), D),
        "lm_head": dense(next(ks), (D, V), D),
        "blocks": blocks,
    }


# ---------------------------------------------------------------- layers

def rope(x, positions, rope_dim: int, theta: float):
    """Rotate-half rotary embedding on the first ``rope_dim`` dims.
    x: (B, S, H, hd)."""
    half = rope_dim // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs     # (S, half)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rope_dim]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s,
                            x[..., rope_dim:]], axis=-1)


def attention(q, k, v, *, block: int = 256, mm: Callable = C.mm):
    """Causal softmax attention, blocks of query rows.  (B, S, H, hd)."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    block = min(block, S)
    nb = S // block

    @jax.checkpoint
    def rows(args):
        qb, t0 = args
        t = t0 + jnp.arange(block)
        s = mm("bqhd,bshd->bhqs", qb, k) / math.sqrt(hd)
        s = jnp.where(t[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return mm("bhqs,bshd->bqhd", jax.nn.softmax(s, axis=-1), v)

    qb = q.reshape(B, nb, block, H, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(rows, (qb, jnp.arange(nb) * block))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def layer(x, p, cfg, mm):
    B, S, D = x.shape
    H, Kv, hd = cfg["n_heads"], cfg["n_kv_heads"], _hd(cfg)
    a = p["attn"]
    h = C.layernorm(x, p["attn_norm"], cfg["norm_eps"])
    q = (mm("bsd,dh->bsh", h, a["wq"]) + a["bq"]).reshape(B, S, H, hd)
    k = (mm("bsd,dh->bsh", h, a["wk"]) + a["bk"]).reshape(B, S, Kv, hd)
    v = (mm("bsd,dh->bsh", h, a["wv"]) + a["bv"]).reshape(B, S, Kv, hd)
    pos = jnp.arange(S)
    rd = _rope_dim(cfg)
    q = rope(q, pos, rd, cfg["rope_theta"])
    k = rope(k, pos, rd, cfg["rope_theta"])
    o = attention(q, k, v, mm=mm).reshape(B, S, H * hd)
    x = x + mm("bsh,hd->bsd", o, a["wo"])
    f = p["ffn"]
    h = C.layernorm(x, p["ffn_norm"], cfg["norm_eps"])
    g = jax.nn.silu(mm("bsd,df->bsf", h, f["w_gate"])) \
        * mm("bsd,df->bsf", h, f["w_up"])
    return x + mm("bsf,fd->bsd", g, f["w_down"])


def loss(params: Params, tokens, targets, cfg, *, mm: Callable = C.mm):
    """Mean next-token cross-entropy plus z-loss, all in float32."""
    x = params["embed"][tokens]
    for i in range(cfg["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["blocks"])
        x = jax.checkpoint(layer, static_argnums=(2, 3))(x, lp, cfg, mm)
    x = C.layernorm(x, params["final_norm"], cfg["norm_eps"])
    return C.lm_loss(x, params["lm_head"], targets, cfg["z_loss"], mm=mm)


# ---------------------------------------------------------------- FLOPs

def flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs of one token's forward pass, averaged over a sequence
    of ``seq_len``: the projections, the SwiGLU MLP, the head, and
    causal attention (a token attends to itself and the positions before
    it, (seq_len + 1) / 2 on average).  Backward is twice this."""
    D, F, V, L = (cfg["d_model"], cfg["d_ff"], cfg["vocab_size"],
                  cfg["n_layers"])
    H, Kv, hd = cfg["n_heads"], cfg["n_kv_heads"], _hd(cfg)
    proj = 2 * D * (H * hd + 2 * Kv * hd) + 2 * H * hd * D
    mlp = 3 * 2 * D * F
    attn = 2 * 2 * H * hd * (seq_len + 1) / 2
    return L * (proj + mlp + attn) + 2 * D * V


def flops_per_step(cfg, batch: int, seq_len: int) -> float:
    """Forward and backward, no recomputation: 3x the forward."""
    return 3.0 * batch * seq_len * flops_per_token(cfg, seq_len)
