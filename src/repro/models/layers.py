"""Core neural-net primitives (pure functional, pytree params).

All matmul-bearing ops keep params in bf16 and compute norms/softmax/router
logits in f32.  Tensors are annotated with logical-axis sharding constraints
(`repro.sharding.shard`) which resolve to physical mesh axes under a rules
context and to no-ops on a single device.
"""
from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import shard

DEFAULT_DTYPE = jnp.bfloat16

def _p_tile_bf16() -> bool:
    """§Perf knob: bf16 probability tiles in blocked attention (read at
    trace time so launchers can set it per-invocation)."""
    return os.environ.get("REPRO_ATTN_P_BF16", "0") == "1"


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, dtype=DEFAULT_DTYPE,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out),
                                        jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, *, dtype=DEFAULT_DTYPE) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d),
                                        jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jax.Array, weight: jax.Array, bias: Optional[jax.Array],
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def norm_apply(x, p, kind: str, eps: float):
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"], eps)
    return layernorm(x, p["w"], p.get("b"), eps)


def norm_init(d: int, kind: str, dtype=jnp.float32):
    p = {"w": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["b"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# rotary embeddings (partial-rotary supported)
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2's
    ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    """The rotary dimension whose wavelength fits ``rotations`` turns
    into ``max_pos`` positions."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_inv_freq(rope_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies (rope_dim//2,).  With a YaRN ``scaling``
    (``configs.base.YaRNConfig``) the high frequencies, which turn more
    than ``beta_fast`` times over the original context, keep their value
    (extrapolation), those turning fewer than ``beta_slow`` times are
    divided by ``factor`` (interpolation), and a linear ramp over the
    dimensions between blends the two."""
    half = rope_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is None or scaling.factor <= 1.0:
        return freqs
    n = scaling.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(
        scaling.beta_fast, rope_dim, theta, n)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        scaling.beta_slow, rope_dim, theta, n)), rope_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def rope_angles(positions: jax.Array, rope_dim: int, theta: float,
                scaling=None) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int -> cos/sin of shape (..., rope_dim//2).
    ``scaling``: a ``YaRNConfig`` or None (see ``rope_inv_freq``); YaRN
    also scales cos and sin by ``mscale(mscale) / mscale(mscale_all_dim)``."""
    freqs = rope_inv_freq(rope_dim, theta, scaling)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.factor > 1.0:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               rope_dim: int) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, rope_dim//2) or (S, rope_dim//2)."""
    if rope_dim == 0:
        return x
    rot, rest = x[..., :rope_dim], x[..., rope_dim:]
    half = rope_dim // 2
    x1, x2 = rot[..., :half], rot[..., half:]
    if cos.ndim == 2:            # (S, half) -> broadcast over batch & heads
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    else:                         # (B, S, half)
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = x1f * c - x2f * s
    o2 = x2f * c + x1f * s
    out = jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, rest], axis=-1) if rest.shape[-1] else out


# ---------------------------------------------------------------------------
# attention cores
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """Grouped-query scores without materializing repeated KV.

    q: (B, Sq, Kv, G, D), k: (B, Sk, Kv, D) -> (B, Kv, G, Sq, Sk) f32
    """
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p: (B, Kv, G, Sq, Sk) f32; v: (B, Sk, Kv, D) -> (B, Sq, Kv, G, D).

    Probabilities stay f32 and the PV product accumulates in f32 (flash-
    kernel convention); rounding p to bf16 costs ~0.4% per weight, which
    is what pushed the expanded-vs-absorbed MLA logit diff over tolerance.
    """
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                      preferred_element_type=jnp.float32)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, q_offset: int = 0,
                      block_q: int = 512, block_k: int = 1024,
                      softcap: float = 0.0,
                      scale: Optional[float] = None) -> jax.Array:
    """Memory-bounded online-softmax attention (pure jnp; flash-style).

    q: (B, Sq, H, D); k/v: (B, Sk, Kv, D).  GQA handled by grouped einsum (no
    KV repetition).  The Pallas flash kernel is the TPU production path; this
    is the XLA fallback / oracle with identical math.  ``scale`` multiplies
    the scores (default ``1/sqrt(D)``).
    """
    B, Sq, H, D = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    G = H // Kv
    scale = 1.0 / math.sqrt(D) if scale is None else scale

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    while Sq % block_q:            # non-power-of-two seqs (whisper's 1500)
        block_q -= 1
    while Sk % block_k:
        block_k -= 1
    nq, nk = Sq // block_q, Sk // block_k

    qr = q.reshape(B, nq, block_q, Kv, G, D)
    kr = k.reshape(B, nk, block_k, Kv, D)
    vr = v.reshape(B, nk, block_k, Kv, Dv)

    q_pos_base = jnp.arange(block_q)
    k_pos_base = jnp.arange(block_k)

    def q_block(carry, inputs):
        qi, qb = inputs            # qb: (B, block_q, Kv, G, D)
        q_pos = q_offset + qi * block_q + q_pos_base

        def kv_block(acc, kin):
            ki, kb, vb = kin
            m_prev, l_prev, o_prev = acc
            s = _gqa_scores(qb, kb) * scale      # (B,Kv,G,bq,bk) f32
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            if causal:
                k_pos = ki * block_k + k_pos_base
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            if _p_tile_bf16():
                # p tile in bf16 for the PV matmul (flash-kernel
                # practice): halves probability-tile traffic; the
                # accumulator stays f32 (§Perf knob REPRO_ATTN_P_BF16)
                pv = jnp.einsum("bkgqs,bskd->bkgqd",
                                p.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32)
            else:
                pv = jnp.einsum("bkgqs,bskd->bkgqd", p,
                                vb.astype(jnp.float32))
            o_new = o_prev * corr[..., None] + pv
            return (m_new, l_new, o_new), None

        init = (jnp.full((B, Kv, G, block_q), -1e30, jnp.float32),
                jnp.zeros((B, Kv, G, block_q), jnp.float32),
                jnp.zeros((B, Kv, G, block_q, Dv), jnp.float32))
        # checkpoint the kv block: backward recomputes the (bq, bk) score
        # tile instead of saving it — the flash-attention memory pattern
        (m, l, o), _ = jax.lax.scan(
            jax.checkpoint(kv_block), init,
            (jnp.arange(nk), jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        o = o / jnp.maximum(l[..., None], 1e-30)
        # (B,Kv,G,bq,D) -> (B,bq,Kv,G,D)
        return carry, jnp.moveaxis(o, 3, 1)

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq),
                                           jnp.moveaxis(qr, 1, 0)))
    # outs: (nq, B, bq, Kv, G, Dv) -> (B, Sq, H, Dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Kv, G, Dv)
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


def full_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                   softcap: float = 0.0,
                   scale: Optional[float] = None) -> jax.Array:
    """Unblocked reference attention (small shapes / oracles)."""
    B, Sq, H, D = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q.reshape(B, Sq, Kv, G, D)
    if scale is None:
        s = _gqa_scores(qg, k) / math.sqrt(D)
    else:
        s = _gqa_scores(qg, k) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    if causal:
        q_pos = q_offset + jnp.arange(Sq)
        mask = q_pos[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_out(p, v)
    return o.reshape(B, Sq, H, v.shape[3]).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     softcap: float = 0.0) -> jax.Array:
    """One-token attention against a (possibly sequence-sharded) KV cache.

    q: (B, 1, H, D); caches: (B, S, Kv, D); cache_len: scalar int (valid
    prefix length, new token already written at cache_len-1).
    """
    B, _, H, D = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    G = H // Kv
    qg = q.reshape(B, 1, Kv, G, D)
    s = _gqa_scores(qg, k_cache) / math.sqrt(D)   # (B,Kv,G,1,S)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    valid = jnp.arange(S)[None, :] < cache_len    # broadcast (1,S) or (B,S)
    if valid.ndim == 2 and valid.shape[0] == 1:
        mask = valid[0][None, None, None, None, :]
    else:
        mask = valid[:, None, None, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_out(p, v_cache)
    return o.reshape(B, 1, H, v_cache.shape[3]).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, act: str, dtype=DEFAULT_DTYPE):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[1], d_model, d_ff, dtype=dtype),
         "w_down": dense_init(ks[2], d_ff, d_model, dtype=dtype)}
    if act == "silu":             # SwiGLU
        p["w_gate"] = dense_init(ks[0], d_model, d_ff, dtype=dtype)
    return p


def mlp_apply(x, p, act: str):
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if act == "silu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    h = shard(h, "batch", None, "ff")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


def mlp_logical_axes(act: str):
    p = {"w_up": ("embed", "ff"), "w_down": ("ff", "embed")}
    if act == "silu":
        p["w_gate"] = ("embed", "ff")
    return p


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, targets: jax.Array,
                 z_loss: float = 1e-4) -> Tuple[jax.Array, jax.Array]:
    """logits (B,S,V) any dtype; targets (B,S) int.  Returns (loss, zl)."""
    lf = logits.astype(jnp.float32)
    # the shift must be detached on BOTH sides: subtracting sg(m) but
    # adding back a live m leaks an extra +1 into the argmax logit's
    # gradient (d lse/dl = softmax + one_hot(argmax)), which suppresses
    # whichever logit is currently winning and stalls training
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    zl = z_loss * jnp.square(lse)
    return jnp.mean(nll), jnp.mean(zl)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def causal_conv1d(x: jax.Array, w: jax.Array,
                  state: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv.  x: (B, L, C); w: (C, K).

    If ``state`` (B, K-1, C) is given it is prepended (decode path).
    """
    K = w.shape[1]
    if state is None:
        pad = jnp.zeros(x.shape[:1] + (K - 1,) + x.shape[2:], x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)              # (B, L+K-1, C)
    stack = jnp.stack([xp[:, i:i + x.shape[1]] for i in range(K)],
                      axis=-1)                          # (B, L, C, K)
    return jnp.einsum("blck,ck->blc", stack, w.astype(x.dtype))
