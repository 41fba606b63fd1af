"""Attention modules: GQA (dense archs) and MLA (DeepSeek-V2).

Each module provides init / logical_axes / train-prefill apply / decode apply
and its cache layout.  MLA decode uses the *absorbed* formulation so only the
compressed (c_kv, k_rope) cache is ever materialized — the memory win that
makes deepseek-v2-lite decode_32k cheap (§Roofline).

Decode against a long sequence-sharded KV cache uses a flash-decode style
shard_map: each model shard computes a chunked partial softmax over its
local KV slice; partials merge with (pmax, rescale, psum) — peak scores
memory drops from O(S) to O(chunk) per chip.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels.flash_attention import flash_attention
from repro.models import layers as L
from repro import parallel as PX
from repro.sharding import current_rules, shard


def _kv_seq_axes():
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return (), None
    ax = rules.rules.get("kv_seq")
    if ax is None:
        return (), rules
    return PX.axis_tuple(ax), rules


def _local_partial_softmax(q, k, v, valid, *, chunk: int = 1024,
                           softcap: float = 0.0):
    """Online-softmax partials over the local KV slice.

    q: (B,1,Kv,G,D); k/v: (B,Sl,Kv,Dv); valid: (Sl,) bool.
    Returns (m, l, acc): (B,Kv,G,1[,Dv]) f32 partial stats.
    """
    B, Sl, Kv, D = k.shape
    Dv = v.shape[-1]
    G = q.shape[3]
    scale = 1.0 / math.sqrt(q.shape[-1])
    while Sl % chunk:
        chunk -= 1
    n = Sl // chunk
    kr = k.reshape(B, n, chunk, Kv, D)
    vr = v.reshape(B, n, chunk, Kv, Dv)
    vm = valid.reshape(n, chunk)

    def body(carry, inp):
        m0, l0, a0 = carry
        kb, vb, vb_mask = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, kb,
                       preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(vb_mask[None, None, None, None, :], s, -1e30)
        m1 = jnp.maximum(m0, jnp.max(s, axis=-1))
        p = jnp.exp(s - m1[..., None])
        corr = jnp.exp(m0 - m1)
        l1 = l0 * corr + jnp.sum(p, axis=-1)
        a1 = a0 * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vb.astype(jnp.float32))
        return (m1, l1, a1), None

    init = (jnp.full((B, Kv, G, 1), -1e30, jnp.float32),
            jnp.zeros((B, Kv, G, 1), jnp.float32),
            jnp.zeros((B, Kv, G, 1, Dv), jnp.float32))
    (m, l, a), _ = jax.lax.scan(
        body, init, (jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0), vm))
    return m, l, a


def sharded_decode_attention(q, k_cache, v_cache, pos, *,
                             softcap: float = 0.0):
    """Flash-decode over a kv_seq-sharded cache; falls back to the dense
    path when no kv_seq sharding rule is active."""
    seq_axes, rules = _kv_seq_axes()
    B, _, H, D = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // Kv
    qg = q.reshape(B, 1, Kv, G, D)

    mesh = rules.mesh if rules is not None else None
    n_shards = PX.axes_size(mesh, seq_axes) if seq_axes else 1
    if n_shards == 1 or S % n_shards:
        # single-shard chunked path (still O(chunk) memory)
        valid = jnp.arange(S) < pos + 1
        m, l, acc = _local_partial_softmax(qg, k_cache, v_cache, valid,
                                           softcap=softcap)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(B, 1, H, Dv).astype(q.dtype)

    S_loc = S // n_shards
    # every mesh axis is mapped manually (partially-auto shard_maps crash
    # XLA's SPMD partitioner on older JAX), so the batch sharding must be
    # spelled out explicitly; axes that don't divide B stay replicated,
    # mirroring sharding.shard()'s drop rule
    batch_ax = tuple(a for a in PX.axis_tuple(rules.rules.get("kv_batch"))
                     if a not in seq_axes)
    if not batch_ax or B % PX.axes_size(mesh, batch_ax):
        batch_ax = None
    # each shard's KV start offset rides in as a P(seq_axes)-sharded
    # operand instead of axis_index arithmetic: axis_index lowers to a
    # PartitionId op some XLA versions reject, a sharded iota never is
    starts = (jnp.arange(n_shards, dtype=jnp.int32) * S_loc)

    def mapped(qg, k, v, pos, start):
        valid = (start[0] + jnp.arange(S_loc)) < pos + 1
        m, l, acc = _local_partial_softmax(qg, k, v, valid,
                                           softcap=softcap)
        gm = PX.pmax(m, seq_axes)
        corr = jnp.exp(m - gm)
        l = PX.psum(l * corr, seq_axes)
        acc = PX.psum(acc * corr[..., None], seq_axes)
        return acc / jnp.maximum(l[..., None], 1e-30)

    out = PX.shard_map(
        mapped, mesh=mesh,
        in_specs=(P(batch_ax), P(batch_ax, seq_axes, None, None),
                  P(batch_ax, seq_axes, None, None), P(), P(seq_axes)),
        out_specs=P(batch_ax),
        check_vma=False,
    )(qg, k_cache, v_cache, pos, starts)
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ArchConfig, dtype=L.DEFAULT_DTYPE):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, Kv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, H * hd, dtype=dtype),
        "wk": L.dense_init(ks[1], d, Kv * hd, dtype=dtype),
        "wv": L.dense_init(ks[2], d, Kv * hd, dtype=dtype),
        "wo": L.dense_init(ks[3], H * hd, d, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Kv * hd,), dtype)
        p["bv"] = jnp.zeros((Kv * hd,), dtype)
    return p


def gqa_logical_axes(cfg: ArchConfig):
    p = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
         "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
    if cfg.qkv_bias:
        p.update({"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)})
    return p


def _qkv(x, p, cfg: ArchConfig):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v


def _rope_dims(cfg: ArchConfig) -> int:
    if not cfg.use_rope:
        return 0
    hd = cfg.resolved_head_dim
    rd = int(hd * cfg.rope_fraction)
    return rd - (rd % 2)


def use_flash_kernel(q, k, softcap: float) -> bool:
    """Whether ``gqa_apply`` and ``mla_apply`` run the Pallas
    flash-attention kernel (forward and backward) in place of
    ``L.blocked_attention``.

    Only where the kernel covers the case on one device: the backend is
    TPU, self-attention (Sq == Sk, so no ``kv_override``), S a multiple
    of 128 (the kernel's lane tiling; whisper's 1500 is not), no logit
    softcap, and no mesh of more than one device (the operands are not
    split).  q: (B, Sq, H, D); k: (B, Sk, Kv, D).
    """
    S = q.shape[1]
    if (jax.default_backend() != "tpu" or S != k.shape[1] or S % 128
            or softcap):
        return False
    rules = current_rules()
    return rules is None or rules.mesh is None or rules.mesh.size == 1


def gqa_apply(x, p, cfg: ArchConfig, *, positions: jax.Array,
              causal: bool = True,
              kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
              block_q: int = 512, block_k: int = 1024) -> jax.Array:
    """Training / prefill attention.  x: (B,S,D); positions: (S,)."""
    q, k, v = _qkv(x, p, cfg)
    rd = _rope_dims(cfg)
    if rd and kv_override is None:
        cos, sin = L.rope_angles(positions, rd, cfg.rope_theta,
                                 cfg.rope_scaling)
        q = L.apply_rope(q, cos, sin, rd)
        k = L.apply_rope(k, cos, sin, rd)
    elif rd:
        cos, sin = L.rope_angles(positions, rd, cfg.rope_theta,
                                 cfg.rope_scaling)
        q = L.apply_rope(q, cos, sin, rd)
    if kv_override is not None:   # cross-attention: encoder / media KV
        k, v = kv_override
        causal = False
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    if q.shape[1] * k.shape[1] <= 1024 * 1024:
        o = L.full_attention(q, k, v, causal=causal,
                             softcap=cfg.logit_softcap)
    elif use_flash_kernel(q, k, cfg.logit_softcap):
        with jax.named_scope("flash_attention"):
            o = flash_attention(q, k, v, causal=causal)
    else:
        o = L.blocked_attention(q, k, v, causal=causal, block_q=block_q,
                                block_k=block_k, softcap=cfg.logit_softcap)
    o = o.reshape(x.shape[0], x.shape[1], -1)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"])


def gqa_make_cache(cfg: ArchConfig, batch: int, seq: int, n_layers: int,
                   dtype=L.DEFAULT_DTYPE):
    hd = cfg.resolved_head_dim
    shape = (n_layers, batch, seq, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_cache_axes():
    return {"k": (None, "kv_batch", "kv_seq", None, None),
            "v": (None, "kv_batch", "kv_seq", None, None)}


def gqa_decode(x, p, cfg: ArchConfig, k_cache, v_cache, pos):
    """x: (B,1,D); caches (B,S,Kv,hd); pos: scalar index of the new token.

    Returns (out, new_k_entry, new_v_entry) — the caller owns cache updates
    (they live in a layer-stacked array updated inside the scan).
    """
    q, k, v = _qkv(x, p, cfg)
    rd = _rope_dims(cfg)
    if rd:
        posv = jnp.asarray(pos)[None]
        cos, sin = L.rope_angles(posv, rd, cfg.rope_theta,
                                 cfg.rope_scaling)
        q = L.apply_rope(q, cos, sin, rd)
        k = L.apply_rope(k, cos, sin, rd)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), pos, axis=1)
    k_cache = shard(k_cache, "kv_batch", "kv_seq", None, None)
    v_cache = shard(v_cache, "kv_batch", "kv_seq", None, None)
    o = sharded_decode_attention(q, k_cache, v_cache, pos,
                                 softcap=cfg.logit_softcap)
    o = o.reshape(x.shape[0], 1, -1)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ArchConfig, dtype=L.DEFAULT_DTYPE):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": L.dense_init(ks[0], d, H * qd, dtype=dtype),
        "w_dkv": L.dense_init(ks[1], d, m.kv_lora_rank, dtype=dtype),
        "w_krope": L.dense_init(ks[2], d, m.qk_rope_dim, dtype=dtype),
        "w_uk": L.dense_init(ks[3], m.kv_lora_rank, H * m.qk_nope_dim,
                             dtype=dtype),
        "w_uv": L.dense_init(ks[4], m.kv_lora_rank, H * m.v_head_dim,
                             dtype=dtype),
        "wo": L.dense_init(ks[5], H * m.v_head_dim, d, dtype=dtype),
        "kv_norm": L.norm_init(m.kv_lora_rank, "rmsnorm"),
    }


def mla_logical_axes(cfg: ArchConfig):
    return {
        "wq": ("embed", "heads"),
        "w_dkv": ("embed", "lora"),
        "w_krope": ("embed", None),
        "w_uk": ("lora", "heads"),
        "w_uv": ("lora", "heads"),
        "wo": ("heads", "embed"),
        "kv_norm": {"w": (None,)},
    }


def _mla_qc(x, p, cfg: ArchConfig, positions):
    """Shared q / compressed-kv computation.  Returns q_nope,q_rope,c_kv,k_rope."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, H, qd)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    c_kv = jnp.einsum("bsd,dc->bsc", x, p["w_dkv"])
    c_kv = L.rmsnorm(c_kv, p["kv_norm"]["w"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_krope"])[:, :, None, :]
    cos, sin = L.rope_angles(positions, m.qk_rope_dim, cfg.rope_theta,
                             cfg.rope_scaling)
    q_rope = L.apply_rope(q_rope, cos, sin, m.qk_rope_dim)
    k_rope = L.apply_rope(k_rope, cos, sin, m.qk_rope_dim)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """``1/sqrt(qk dims)``, times ``mscale(factor, mscale_all_dim)**2``
    under YaRN (DeepSeek-V2's attention temperature)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    r = cfg.rope_scaling
    if r is not None and r.mscale_all_dim:
        scale *= L.yarn_mscale(r.factor, r.mscale_all_dim) ** 2
    return scale


def mla_apply(x, p, cfg: ArchConfig, *, positions, causal: bool = True,
              block_q: int = 512, block_k: int = 1024):
    """Expanded (train/prefill) MLA: materialize per-head K,V from c_kv.
    On one TPU (``use_flash_kernel``) the flash kernel takes q and k at
    qk_nope + qk_rope dims and v at v_head_dim."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qc(x, p, cfg, positions)
    k_nope = jnp.einsum("bsc,ch->bsh", c_kv, p["w_uk"]).reshape(
        B, S, H, m.qk_nope_dim)
    v = jnp.einsum("bsc,ch->bsh", c_kv, p["w_uv"]).reshape(
        B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_dim))], axis=-1)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "heads", None)
    v = shard(v, "batch", None, "heads", None)
    scale = mla_softmax_scale(cfg)
    if S * S <= 1024 * 1024:
        o = L.full_attention(q, k, v, causal=causal, scale=scale)
    elif use_flash_kernel(q, k, 0.0):
        with jax.named_scope("flash_attention"):
            o = flash_attention(q, k, v, causal=causal, scale=scale)
    else:
        o = L.blocked_attention(q, k, v, causal=causal, block_q=block_q,
                                block_k=block_k, scale=scale)
    o = o.reshape(B, S, -1)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"])


def mla_make_cache(cfg: ArchConfig, batch: int, seq: int, n_layers: int,
                   dtype=L.DEFAULT_DTYPE):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((n_layers, batch, seq, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((n_layers, batch, seq, m.qk_rope_dim), dtype),
    }


def mla_cache_axes():
    return {"c_kv": (None, "kv_batch", "kv_seq", "lora"),
            "k_rope": (None, "kv_batch", "kv_seq", None)}


def mla_decode(x, p, cfg: ArchConfig, ckv_cache, krope_cache, pos):
    """Absorbed-matmul MLA decode: attention runs in the 512-d latent space.

    scores = (q_nope @ W_uk^T) @ c_kv^T + q_rope @ k_rope^T
    out    = (probs @ c_kv) @ W_uv
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    posv = jnp.asarray(pos)[None]
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qc(x, p, cfg, posv)
    ckv_cache = jax.lax.dynamic_update_slice_in_dim(
        ckv_cache, c_kv_new.astype(ckv_cache.dtype), pos, axis=1)
    krope_cache = jax.lax.dynamic_update_slice_in_dim(
        krope_cache, k_rope_new.astype(krope_cache.dtype), pos, axis=1)
    ckv_cache = shard(ckv_cache, "kv_batch", "kv_seq", "lora")
    krope_cache = shard(krope_cache, "kv_batch", "kv_seq", None)

    # latent-space matmuls in f32: decode batches are small and the
    # absorbed reordering through the 512-d latent loses too much in bf16
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    q_lat = jnp.einsum("bqhn,chn->bqhc", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))          # (B,1,H,C)
    s = (jnp.einsum("bqhc,bsc->bhqs", q_lat,
                    ckv_cache.astype(jnp.float32))
         + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                      krope_cache.astype(jnp.float32)))
    s = s * mla_softmax_scale(cfg)
    valid = jnp.arange(ckv_cache.shape[1])[None, None, None, :] < pos + 1
    s = jnp.where(valid, s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqs,bsc->bqhc", prob,
                       ckv_cache.astype(jnp.float32))     # (B,1,H,C)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bqhc,chv->bqhv", o_lat,
                   w_uv.astype(jnp.float32)).reshape(B, 1, -1)
    out = jnp.einsum("bsh,hd->bsd", o.astype(x.dtype), p["wo"])
    return out, ckv_cache, krope_cache
