"""Unified transformer LM covering the dense / vlm / moe / encdec families.

One scanned layer stack (params stacked on a leading layer axis) keeps the
HLO size independent of depth — essential for fast multi-pod dry-run compiles
of the 100-layer archs.  Heterogeneous stacks (VLM cross-attn every Nth
layer) scan over *superblocks*.

Public surface (shared by all model classes in this package):
    init(rng) -> params
    param_logical_axes() -> pytree of logical-axis tuples (same treedef)
    loss(params, batch) -> (loss, metrics)
    forward_logits(params, batch) -> (logits, stats)   (train fwd / prefill)
    init_cache(batch_size, seq_len) -> cache
    cache_logical_axes(...)
    prefill(params, batch, cache) -> (logits, cache)
    decode_step(params, cache, tokens, pos) -> (logits, cache)
    input_specs(shape) -> dict[str, ShapeDtypeStruct]
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import layers as L
from repro.sharding import shard

Params = Any


def _use_mla(cfg: ArchConfig) -> bool:
    return cfg.mla is not None


def _is_moe_layer(cfg: ArchConfig, layer_idx: int) -> bool:
    return (cfg.moe is not None
            and layer_idx >= cfg.moe.first_dense_layers)


# ---------------------------------------------------------------------------
# single layer init/apply
# ---------------------------------------------------------------------------

def layer_init(key, cfg: ArchConfig, *, moe: bool, cross: bool = False,
               dense_ff: Optional[int] = None):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"attn_norm": L.norm_init(cfg.d_model, cfg.norm)}
    if cross:
        p["attn"] = A.gqa_init(ks[0], cfg)
    elif _use_mla(cfg):
        p["attn"] = A.mla_init(ks[0], cfg)
    else:
        p["attn"] = A.gqa_init(ks[0], cfg)
    if not cfg.parallel_block:
        p["ffn_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    if moe:
        p["ffn"] = F.moe_init(ks[1], cfg)
    else:
        p["ffn"] = L.mlp_init(ks[1], cfg.d_model, dense_ff or cfg.d_ff,
                              cfg.act)
    if cross:
        p["xgate"] = jnp.zeros((), jnp.float32)   # tanh-gated cross-attn
    return p


def layer_logical_axes(cfg: ArchConfig, *, moe: bool, cross: bool = False):
    p: Dict[str, Any] = {
        "attn_norm": _norm_axes(cfg),
    }
    if cross or not _use_mla(cfg):
        p["attn"] = A.gqa_logical_axes(cfg)
    else:
        p["attn"] = A.mla_logical_axes(cfg)
    if not cfg.parallel_block:
        p["ffn_norm"] = _norm_axes(cfg)
    p["ffn"] = F.moe_logical_axes(cfg) if moe else L.mlp_logical_axes(cfg.act)
    if cross:
        p["xgate"] = ()
    return p


def _norm_axes(cfg: ArchConfig):
    return ({"w": (None,), "b": (None,)} if cfg.norm == "layernorm"
            else {"w": (None,)})


def layer_apply(x, p, cfg: ArchConfig, *, positions, moe: bool,
                causal: bool = True,
                media_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                cross: bool = False):
    """Returns (x_out, stats): ``ffn.zero_stats`` or the expert layer's."""
    stats = F.zero_stats(cfg)
    h = L.norm_apply(x, p["attn_norm"], cfg.norm, cfg.norm_eps)
    h = shard(h, "batch", None, None)
    with jax.named_scope("attention"):
        if cross:
            a = A.gqa_apply(h, p["attn"], cfg, positions=positions,
                            kv_override=media_kv)
            a = jnp.tanh(p["xgate"]).astype(a.dtype) * a
        elif _use_mla(cfg):
            a = A.mla_apply(h, p["attn"], cfg, positions=positions,
                            causal=causal)
        else:
            a = A.gqa_apply(h, p["attn"], cfg, positions=positions,
                            causal=causal)
    if not cfg.parallel_block:
        x = x + a
        h = L.norm_apply(x, p["ffn_norm"], cfg.norm, cfg.norm_eps)
        h = shard(h, "batch", None, None)
    with jax.named_scope("mlp"):
        if moe:
            f, stats = F.moe_apply(h, p["ffn"], cfg)
        else:
            f = L.mlp_apply(h, p["ffn"], cfg.act)
    x = x + a + f if cfg.parallel_block else x + f
    return shard(x, "batch", "seq", None), stats


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class TransformerLM:
    """dense / moe / vlm / encdec transformer LM."""

    def __init__(self, cfg: ArchConfig, *, remat: bool = True):
        self.cfg = cfg
        self.remat = remat

    # ---------------------------------------------------------------- init
    def init(self, rng) -> Params:
        cfg = self.cfg
        k_emb, k_layers, k_head, k_enc, k_first = jax.random.split(rng, 5)
        p: Dict[str, Any] = {
            "embed": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model),
            "final_norm": L.norm_init(cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_size)

        n_scanned = cfg.n_layers - self._n_first_dense()
        if cfg.family == "vlm":
            p["blocks"] = self._init_vlm_blocks(k_layers)
        else:
            p["blocks"] = self._init_stack(
                k_layers, n_scanned, moe=cfg.moe is not None)
        if self._n_first_dense():
            p["first"] = self._init_stack(
                k_first, self._n_first_dense(), moe=False,
                dense_ff=cfg.moe.dense_d_ff)
        if cfg.is_encdec:
            p["encoder"] = self._init_stack(
                k_enc, cfg.n_enc_layers, moe=False, causal_stack=False)
            p["enc_final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
            ks = jax.random.split(k_enc, 3)
            p["cross_blocks"] = jax.vmap(
                lambda k: A.gqa_init(k, self.cfg))(
                    jax.random.split(ks[1], cfg.n_layers))
            p["cross_norms"] = jax.vmap(
                lambda k: L.norm_init(cfg.d_model, cfg.norm))(
                    jax.random.split(ks[2], cfg.n_layers))
        return p

    def _n_first_dense(self) -> int:
        return self.cfg.moe.first_dense_layers if self.cfg.moe else 0

    def _init_stack(self, key, n, *, moe, dense_ff=None, causal_stack=True):
        keys = jax.random.split(key, max(n, 1))
        return jax.vmap(lambda k: layer_init(
            k, self.cfg, moe=moe, dense_ff=dense_ff))(keys[:n])

    def _init_vlm_blocks(self, key):
        cfg = self.cfg
        n_super = cfg.n_layers // cfg.cross_every
        n_self = cfg.cross_every - 1
        k_self, k_cross = jax.random.split(key)

        def super_init(k):
            ks, kc = jax.random.split(k)
            return {
                "self": jax.vmap(lambda kk: layer_init(
                    kk, cfg, moe=False))(jax.random.split(ks, n_self)),
                "cross": layer_init(kc, cfg, moe=False, cross=True),
            }
        return jax.vmap(super_init)(jax.random.split(key, n_super))

    # ------------------------------------------------------------- axes
    def param_logical_axes(self):
        cfg = self.cfg
        p: Dict[str, Any] = {
            "embed": ("vocab", "embed"),
            "final_norm": _norm_axes(cfg),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = ("embed", "vocab")
        la = layer_logical_axes(cfg, moe=cfg.moe is not None)
        if cfg.family == "vlm":
            p["blocks"] = {
                "self": _stacked(layer_logical_axes(cfg, moe=False)),
                "cross": _stacked(
                    layer_logical_axes(cfg, moe=False, cross=True)),
            }
            # inner 'self' has two leading stack dims; _stacked adds one
            p["blocks"]["self"] = jax.tree.map(
                lambda ax: (None,) + ax if isinstance(ax, tuple) else ax,
                p["blocks"]["self"], is_leaf=lambda v: isinstance(v, tuple))
        else:
            p["blocks"] = _stacked(la)
        if self._n_first_dense():
            p["first"] = _stacked(layer_logical_axes(
                cfg, moe=False))
        if cfg.is_encdec:
            p["encoder"] = _stacked(layer_logical_axes(cfg, moe=False))
            p["enc_final_norm"] = _norm_axes(cfg)
            p["cross_blocks"] = _stacked(A.gqa_logical_axes(cfg))
            p["cross_norms"] = _stacked(_norm_axes(cfg))
        return p

    # ------------------------------------------------------------ forward
    def _stack_apply(self, x, stacked, *, positions, moe, causal=True):
        cfg = self.cfg

        def body(carry, lp):
            xc, stats = carry
            xo, s = layer_apply(xc, lp, cfg, positions=positions, moe=moe,
                                causal=causal)
            return (xo, F.merge_stats(stats, s)), None

        f = jax.checkpoint(body) if self.remat else body
        (x, stats), _ = jax.lax.scan(f, (x, F.zero_stats(cfg)), stacked)
        return x, stats

    def _vlm_apply(self, x, blocks, *, positions, media):
        cfg = self.cfg
        # pin media's sharding: without this XLA's SPMD partitioner hits
        # "involuntary full rematerialization" on the fwd/bwd sharding
        # mismatch and all-gathers the media activations across pods once
        # per superblock (§Perf pair C, hypothesis C2)
        media = shard(media, "batch", None, None)

        def super_body(carry, sp):
            xc, stats = carry

            def self_body(c, lp):
                xs, a0 = c
                xo, a = layer_apply(xs, lp, cfg, positions=positions,
                                    moe=False)
                return (xo, F.merge_stats(a0, a)), None

            if self.remat:        # per-layer remat: one layer's gathered
                self_body = jax.checkpoint(self_body)  # weights live at once
            (xc, stats), _ = jax.lax.scan(self_body, (xc, stats), sp["self"])
            # cross layer: media K/V projected by this layer's wk/wv
            pm = sp["cross"]
            B, M, _ = media.shape
            hd = cfg.resolved_head_dim
            mk = jnp.einsum("bmd,dh->bmh", media, pm["attn"]["wk"]).reshape(
                B, M, cfg.n_kv_heads, hd)
            mv = jnp.einsum("bmd,dh->bmh", media, pm["attn"]["wv"]).reshape(
                B, M, cfg.n_kv_heads, hd)
            xc, a = layer_apply(xc, pm, cfg, positions=positions, moe=False,
                                cross=True, media_kv=(mk, mv))
            return (xc, F.merge_stats(stats, a)), None

        f = jax.checkpoint(super_body) if self.remat else super_body
        (x, stats), _ = jax.lax.scan(f, (x, F.zero_stats(cfg)), blocks)
        return x, stats

    def _encode(self, params, frames):
        """Whisper encoder over stubbed frame embeddings (B, F, D)."""
        cfg = self.cfg
        x = frames + L.sinusoidal_positions(
            frames.shape[1], cfg.d_model).astype(frames.dtype)[None]
        positions = jnp.arange(frames.shape[1])
        x, _ = self._stack_apply(x, params["encoder"], positions=positions,
                                 moe=False, causal=False)
        return L.norm_apply(x, params["enc_final_norm"], cfg.norm,
                            cfg.norm_eps)

    def _decoder_encdec(self, params, x, positions, enc_out):
        """Whisper decoder: interleaved (self, cross, mlp) per layer."""
        cfg = self.cfg

        def body(carry, lp):
            xc, stats = carry
            block, xattn, xnorm = lp
            xo, a = layer_apply(xc, block, cfg, positions=positions,
                                moe=False)
            # cross-attention sublayer appended after the standard block
            h = L.norm_apply(xo, xnorm, cfg.norm, cfg.norm_eps)
            B, M, _ = enc_out.shape
            hd = cfg.resolved_head_dim
            mk = jnp.einsum("bmd,dh->bmh", enc_out, xattn["wk"]).reshape(
                B, M, cfg.n_kv_heads, hd)
            mv = jnp.einsum("bmd,dh->bmh", enc_out, xattn["wv"]).reshape(
                B, M, cfg.n_kv_heads, hd)
            c = A.gqa_apply(h, xattn, cfg, positions=positions,
                            kv_override=(mk, mv))
            return (xo + c, F.merge_stats(stats, a)), None

        f = jax.checkpoint(body) if self.remat else body
        (x, stats), _ = jax.lax.scan(
            f, (x, F.zero_stats(cfg)),
            (params["blocks"], params["cross_blocks"],
             params["cross_norms"]))
        return x, stats

    def forward_logits(self, params, batch) -> Tuple[jax.Array, F.Stats]:
        """Returns (logits, stats): the balance loss ``aux`` and, with
        experts, their counters (``ffn.moe_apply``), over all layers."""
        cfg = self.cfg
        tokens = batch["tokens"]
        # gather through f32 so the backward scatter-add (the embed
        # gradient) accumulates in f32 — bf16 scatter accumulation is
        # reduction-order sensitive and breaks accum-invariance
        emb = params["embed"]
        with jax.named_scope("head"):
            x = emb.astype(jnp.float32)[tokens].astype(emb.dtype)  # (B,S,D)
        if not cfg.use_rope and not cfg.is_encdec:
            x = x + L.sinusoidal_positions(
                tokens.shape[1], cfg.d_model).astype(x.dtype)[None]
        x = shard(x, "batch", None, None)
        positions = jnp.arange(tokens.shape[1])
        stats = F.zero_stats(cfg)

        if cfg.is_encdec:
            x = x + L.sinusoidal_positions(
                tokens.shape[1], cfg.d_model).astype(x.dtype)[None]
            enc_out = self._encode(params, batch["frames"])
            x, stats = self._decoder_encdec(params, x, positions, enc_out)
        elif cfg.family == "vlm":
            x, stats = self._vlm_apply(x, params["blocks"],
                                       positions=positions,
                                       media=batch["media"])
        else:
            if "first" in params:
                x, s0 = self._stack_apply(x, params["first"],
                                          positions=positions, moe=False)
                stats = F.merge_stats(stats, s0)
            x, s1 = self._stack_apply(x, params["blocks"],
                                      positions=positions,
                                      moe=cfg.moe is not None)
            stats = F.merge_stats(stats, s1)

        with jax.named_scope("head"):
            x = L.norm_apply(x, params["final_norm"], cfg.norm,
                             cfg.norm_eps)
            logits = self._logits(params, x)
        return logits, stats

    def _logits(self, params, x):
        # f32 accumulation: the loss consumes logits in f32 anyway, and
        # the backward of this einsum is the embed/lm_head gradient,
        # which otherwise picks up partition-order-dependent bf16 noise
        if self.cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                                preferred_element_type=jnp.float32)
        return shard(logits, "batch", None, "vocab")

    def loss(self, params, batch):
        """(loss, metrics); ``metrics["counters"]`` holds the expert
        layer's integer counters (none without experts)."""
        logits, stats = self.forward_logits(params, batch)
        with jax.named_scope("head"):
            nll, zl = L.softmax_xent(logits, batch["targets"])
        aux = stats.pop("aux")
        return nll + zl + aux, {"nll": nll, "z_loss": zl, "aux": aux,
                                "counters": stats}

    # two microbatches' ``counters`` as one step's
    merge_counters = staticmethod(F.merge_stats)

    # ------------------------------------------------------------- decode
    def init_cache(self, batch_size: int, seq_len: int):
        cfg = self.cfg
        n = cfg.n_layers
        if _use_mla(cfg):
            n_moe = n - self._n_first_dense()
            cache = A.mla_make_cache(cfg, batch_size, seq_len, n_moe)
            if self._n_first_dense():
                cache["first"] = A.mla_make_cache(
                    cfg, batch_size, seq_len, self._n_first_dense())
        elif cfg.family == "vlm":
            n_super = cfg.n_layers // cfg.cross_every
            cache = {
                "self": jax.tree.map(
                    lambda a: a.reshape((n_super, cfg.cross_every - 1)
                                        + a.shape[1:]),
                    A.gqa_make_cache(cfg, batch_size, seq_len,
                                     n_super * (cfg.cross_every - 1))),
                "cross_k": jnp.zeros(
                    (n_super, batch_size, cfg.n_media_tokens,
                     cfg.n_kv_heads, cfg.resolved_head_dim), L.DEFAULT_DTYPE),
                "cross_v": jnp.zeros(
                    (n_super, batch_size, cfg.n_media_tokens,
                     cfg.n_kv_heads, cfg.resolved_head_dim), L.DEFAULT_DTYPE),
            }
        elif cfg.is_encdec:
            cache = A.gqa_make_cache(cfg, batch_size, seq_len, cfg.n_layers)
            M = cfg.enc_seq_len
            hd = cfg.resolved_head_dim
            cache["cross_k"] = jnp.zeros(
                (cfg.n_layers, batch_size, M, cfg.n_kv_heads, hd),
                L.DEFAULT_DTYPE)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
        else:
            cache = A.gqa_make_cache(cfg, batch_size, seq_len, cfg.n_layers)
        return cache

    def cache_logical_axes(self):
        cfg = self.cfg
        if _use_mla(cfg):
            axes = A.mla_cache_axes()
            if self._n_first_dense():
                axes = dict(axes)
                axes["first"] = A.mla_cache_axes()
            return axes
        if cfg.family == "vlm":
            base = A.gqa_cache_axes()
            return {
                "self": jax.tree.map(
                    lambda ax: (None,) + ax, base,
                    is_leaf=lambda v: isinstance(v, tuple)),
                "cross_k": (None, "kv_batch", None, None, None),
                "cross_v": (None, "kv_batch", None, None, None),
            }
        axes = dict(A.gqa_cache_axes())
        if cfg.is_encdec:
            axes["cross_k"] = (None, "kv_batch", None, None, None)
            axes["cross_v"] = (None, "kv_batch", None, None, None)
        return axes

    def decode_step(self, params, cache, tokens, pos):
        """tokens: (B, 1); pos: scalar int32.  Returns (logits, cache)."""
        cfg = self.cfg
        x = params["embed"][tokens]
        if not cfg.use_rope:
            pe = L.sinusoidal_positions(int(cache_seq_len(cache)),
                                        cfg.d_model)
            x = x + jax.lax.dynamic_slice_in_dim(
                pe, pos, 1, axis=0).astype(x.dtype)[None]
        x = shard(x, "batch", None, None)
        aux0 = jnp.zeros((), jnp.float32)

        if cfg.is_encdec:
            x, cache = self._decode_encdec(params, cache, x, pos)
        elif cfg.family == "vlm":
            x, cache = self._decode_vlm(params, cache, x, pos)
        elif _use_mla(cfg):
            x, cache = self._decode_mla(params, cache, x, pos)
        else:
            x, cache = self._decode_gqa(params, cache, x, pos)

        x = L.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        return self._logits(params, x), cache

    def _decode_gqa(self, params, cache, x, pos):
        cfg = self.cfg

        def body(x, lp_kv):
            lp, (kc, vc) = lp_kv
            h = L.norm_apply(x, lp["attn_norm"], cfg.norm, cfg.norm_eps)
            a, kc, vc = A.gqa_decode(h, lp["attn"], cfg, kc, vc, pos)
            if cfg.parallel_block:
                f = self._decode_ffn(h, lp)
                x = x + a + f
            else:
                x = x + a
                h2 = L.norm_apply(x, lp["ffn_norm"], cfg.norm, cfg.norm_eps)
                x = x + self._decode_ffn(h2, lp)
            return x, (kc, vc)

        if "first" in params:      # unreached for GQA archs today
            raise NotImplementedError
        x, (ks, vs) = jax.lax.scan(body, x,
                                   (params["blocks"],
                                    (cache["k"], cache["v"])))
        return x, {"k": ks, "v": vs}

    def _decode_ffn(self, h, lp):
        cfg = self.cfg
        if cfg.moe is not None and "router" in lp["ffn"]:
            f, _ = F.moe_apply(h, lp["ffn"], cfg)
            return f
        return L.mlp_apply(h, lp["ffn"], cfg.act)

    def _decode_mla(self, params, cache, x, pos):
        cfg = self.cfg

        def mk_body(moe):
            def body(x, lp_kv):
                lp, (cc, rc) = lp_kv
                h = L.norm_apply(x, lp["attn_norm"], cfg.norm, cfg.norm_eps)
                a, cc, rc = A.mla_decode(h, lp["attn"], cfg, cc, rc, pos)
                x = x + a
                h2 = L.norm_apply(x, lp["ffn_norm"], cfg.norm, cfg.norm_eps)
                if moe:
                    f, _ = F.moe_apply(h2, lp["ffn"], cfg)
                else:
                    f = L.mlp_apply(h2, lp["ffn"], cfg.act)
                return x + f, (cc, rc)
            return body

        if "first" in params:
            x, (c0, r0) = jax.lax.scan(
                mk_body(False), x,
                (params["first"],
                 (cache["first"]["c_kv"], cache["first"]["k_rope"])))
        x, (cs, rs) = jax.lax.scan(
            mk_body(True), x, (params["blocks"],
                               (cache["c_kv"], cache["k_rope"])))
        out = {"c_kv": cs, "k_rope": rs}
        if "first" in params:
            out["first"] = {"c_kv": c0, "k_rope": r0}
        return x, out

    def _decode_vlm(self, params, cache, x, pos):
        cfg = self.cfg

        def super_body(x, inp):
            sp, (kc, vc), xk, xv = inp

            def self_body(x, lp_kv):
                lp, (k1, v1) = lp_kv
                h = L.norm_apply(x, lp["attn_norm"], cfg.norm, cfg.norm_eps)
                a, k1, v1 = A.gqa_decode(h, lp["attn"], cfg, k1, v1, pos)
                x = x + a
                h2 = L.norm_apply(x, lp["ffn_norm"], cfg.norm, cfg.norm_eps)
                return x + L.mlp_apply(h2, lp["ffn"], cfg.act), (k1, v1)

            x, (ks, vs) = jax.lax.scan(self_body, x, (sp["self"], (kc, vc)))
            pm = sp["cross"]
            h = L.norm_apply(x, pm["attn_norm"], cfg.norm, cfg.norm_eps)
            a = A.gqa_apply(h, pm["attn"], cfg,
                            positions=jnp.asarray(pos)[None],
                            kv_override=(xk, xv))
            a = jnp.tanh(pm["xgate"]).astype(a.dtype) * a
            x = x + a
            h2 = L.norm_apply(x, pm["ffn_norm"], cfg.norm, cfg.norm_eps)
            x = x + L.mlp_apply(h2, pm["ffn"], cfg.act)
            return x, (ks, vs)

        x, (ks, vs) = jax.lax.scan(
            super_body, x,
            (params["blocks"], (cache["self"]["k"], cache["self"]["v"]),
             cache["cross_k"], cache["cross_v"]))
        return x, {"self": {"k": ks, "v": vs},
                   "cross_k": cache["cross_k"],
                   "cross_v": cache["cross_v"]}

    def _decode_encdec(self, params, cache, x, pos):
        cfg = self.cfg

        def body(x, inp):
            lp, xattn, xnorm, (kc, vc), xk, xv = inp
            h = L.norm_apply(x, lp["attn_norm"], cfg.norm, cfg.norm_eps)
            a, kc, vc = A.gqa_decode(h, lp["attn"], cfg, kc, vc, pos)
            x = x + a
            hx = L.norm_apply(x, xnorm, cfg.norm, cfg.norm_eps)
            c = A.gqa_apply(hx, xattn, cfg,
                            positions=jnp.asarray(pos)[None],
                            kv_override=(xk, xv))
            x = x + c
            h2 = L.norm_apply(x, lp["ffn_norm"], cfg.norm, cfg.norm_eps)
            return x + L.mlp_apply(h2, lp["ffn"], cfg.act), (kc, vc)

        x, (ks, vs) = jax.lax.scan(
            body, x,
            (params["blocks"], params["cross_blocks"], params["cross_norms"],
             (cache["k"], cache["v"]), cache["cross_k"], cache["cross_v"]))
        return x, {"k": ks, "v": vs, "cross_k": cache["cross_k"],
                   "cross_v": cache["cross_v"]}

    # --------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                     "targets": jax.ShapeDtypeStruct((B, S), i32)}
        elif shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        else:                      # decode
            specs = {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                     "pos": jax.ShapeDtypeStruct((), i32)}
        if cfg.frontend == "patch" and shape.kind != "decode":
            specs["media"] = jax.ShapeDtypeStruct(
                (B, cfg.n_media_tokens, cfg.d_model), L.DEFAULT_DTYPE)
        if cfg.frontend == "audio" and shape.kind != "decode":
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq_len, cfg.d_model), L.DEFAULT_DTYPE)
        return specs


def _stacked(axes_tree):
    """Prepend a None (layer-stack) dim to every axes tuple in the tree."""
    return jax.tree.map(lambda ax: (None,) + ax,
                        axes_tree, is_leaf=lambda v: isinstance(v, tuple))


def cache_seq_len(cache) -> int:
    leaves = jax.tree.leaves(cache)
    return max(l.shape[2] for l in leaves if l.ndim >= 3)
