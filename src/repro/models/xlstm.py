"""xLSTM LM: mLSTM (matrix memory, chunk-parallel) + sLSTM (recurrent) blocks.

mLSTM chunked math (stabilized, see derivation in kernels/mlstm/ref.py):
  carry (C_hat, n_hat, m);  per chunk with log-forget cumsum b_t, a_s=i_s-b_s,
  rm_t = max(m0, cummax(a)_t):
    scores[t,s] = (q_t.k_s/sqrt(d)) * exp(a_s - rm_t)        (s<=t)
    inter[t]    = exp(m0 - rm_t) * (C_hat0^T q_t)
    den[t]      = exp(m0 - rm_t) * (n_hat0.q_t) + sum_s scores[t,s]
    h_t         = (sum_s scores[t,s] v_s + inter[t]) / max(|den_t|, exp(-m_t))
  with m_t = b_t + rm_t; carried C' = exp(m0-R)C + sum_s exp(a_s-R) k_s v_s^T,
  n' likewise, m' = b_end + R, R = rm_{end}.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import layers as L
from repro.models.transformer import _norm_axes, _stacked
from repro.sharding import shard


# ---------------------------------------------------------------------------
# mLSTM cell — chunked (jnp; mirrored by the Pallas kernel)
# ---------------------------------------------------------------------------

def mlstm_chunked(q, k, v, i_raw, f_raw, *, chunk: int, carry=None):
    """q,k,v: (B,S,H,D); i_raw,f_raw: (B,S,H).  Returns (h, carry).

    carry = (C (B,H,D,D) f32, n (B,H,D) f32, m (B,H) f32).
    """
    B, S, H, D = q.shape
    assert S % chunk == 0
    nc = S // chunk
    scale = 1.0 / math.sqrt(D)

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_raw.astype(jnp.float32))   # (B,S,H)
    ii = i_raw.astype(jnp.float32)

    qc = qf.reshape(B, nc, chunk, H, D)
    kc = kf.reshape(B, nc, chunk, H, D)
    vc = vf.reshape(B, nc, chunk, H, D)
    lc = lf.reshape(B, nc, chunk, H)
    ic = ii.reshape(B, nc, chunk, H)

    if carry is None:
        C0 = jnp.zeros((B, H, D, D), jnp.float32)
        n0 = jnp.zeros((B, H, D), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = carry

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def chunk_step(carry, inp):
        C, n, m0 = carry
        qb, kb, vb, lb, ib = inp                  # (B,Q,H,*)
        b = jnp.cumsum(lb, axis=1)                # (B,Q,H)
        a = ib - b
        rm = jnp.maximum(jax.lax.cummax(a, axis=1), m0[:, None, :])
        m_t = b + rm                               # absolute stabilizer

        qk = jnp.einsum("bqhd,bshd->bhqs", qb, kb)
        # masked before the exp: above the diagonal the exponent grows
        # with the forget gates' decay and overflows, and a mask after
        # the exp would send inf * 0 = NaN into the gradient
        w = jnp.exp(jnp.where(tri[None, None],
                              a[:, None, :, :].transpose(0, 3, 1, 2)
                              - rm.transpose(0, 2, 1)[:, :, :, None],
                              -jnp.inf))                  # (B,H,t,s)
        scores = qk * w

        inter_scale = jnp.exp(m0[:, :, None] - rm.transpose(0, 2, 1))
        inter = jnp.einsum("bhdk,bqhd->bhqk", C, qb)           # C^T q
        inter = inter * inter_scale[..., None]
        num = jnp.einsum("bhqs,bshd->bhqd", scores, vb) + inter
        den = (jnp.sum(scores, axis=-1)
               + jnp.einsum("bhd,bqhd->bhq", n, qb) * inter_scale)
        h = num / jnp.maximum(jnp.abs(den),
                              jnp.exp(-m_t).transpose(0, 2, 1))[..., None]

        R = rm[:, -1, :]                           # (B,H)
        decay_in = jnp.exp(a - R[:, None, :])      # per-source weight
        C_new = (C * jnp.exp(m0 - R)[:, :, None, None]
                 + jnp.einsum("bsh,bshd,bshe->bhde", decay_in, kb, vb))
        n_new = (n * jnp.exp(m0 - R)[:, :, None]
                 + jnp.einsum("bsh,bshd->bhd", decay_in, kb))
        m_new = b[:, -1, :] + R
        return (C_new, n_new, m_new), h.transpose(0, 2, 1, 3)  # (B,Q,H,D)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (qc, kc, vc, lc, ic))
    # checkpointed: backward recomputes the (Q,Q) gate/score tiles
    (C, n, m), hs = jax.lax.scan(jax.checkpoint(chunk_step),
                                 (C0, n0, m0), xs)
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, D)
    return h.astype(q.dtype), (C, n, m)


def mlstm_step(q, k, v, i_raw, f_raw, carry):
    """Single-token mLSTM.  q,k,v: (B,H,D); gates: (B,H)."""
    C, n, m = carry
    D = q.shape[-1]
    qf = q.astype(jnp.float32) / math.sqrt(D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_raw.astype(jnp.float32))
    ii = i_raw.astype(jnp.float32)
    m_new = jnp.maximum(lf + m, ii)
    i_s = jnp.exp(ii - m_new)
    f_s = jnp.exp(lf + m - m_new)
    C = C * f_s[..., None, None] + i_s[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", kf, vf)
    n = n * f_s[..., None] + i_s[..., None] * kf
    num = jnp.einsum("bhde,bhd->bhe", C, qf)
    den = jnp.einsum("bhd,bhd->bh", n, qf)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h.astype(q.dtype), (C, n, m_new)


def mlstm_sequential_ref(q, k, v, i_raw, f_raw, carry=None):
    """Token-by-token oracle (tests only)."""
    B, S, H, D = q.shape
    if carry is None:
        carry = (jnp.zeros((B, H, D, D), jnp.float32),
                 jnp.zeros((B, H, D), jnp.float32),
                 jnp.full((B, H), -1e30, jnp.float32))

    def step(c, inp):
        qt, kt, vt, it, ft = inp
        h, c = mlstm_step(qt, kt, vt, it, ft, c)
        return c, h

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, i_raw, f_raw))
    carry, hs = jax.lax.scan(step, carry, xs)
    return jnp.moveaxis(hs, 0, 1), carry


# ---------------------------------------------------------------------------
# sLSTM cell (recurrent)
# ---------------------------------------------------------------------------

def slstm_scan(x_gates, r_w, carry):
    """x_gates: (B,S,H,4,Dh) pre-computed input contributions.
    r_w: (H,4,Dh,Dh) recurrent weights.  carry: (c,n,m,h) each (B,H,Dh)."""

    def step(carry, xg):
        c, n, m, h = carry
        rec = jnp.einsum("bhd,hgde->bhge", h, r_w.astype(jnp.float32))
        g = xg.astype(jnp.float32) + rec            # (B,H,4,Dh)
        i_raw, f_raw, z_raw, o_raw = (g[:, :, 0], g[:, :, 1],
                                      g[:, :, 2], g[:, :, 3])
        lf = jax.nn.log_sigmoid(f_raw)
        m_new = jnp.maximum(lf + m, i_raw)
        i_s = jnp.exp(i_raw - m_new)
        f_s = jnp.exp(lf + m - m_new)
        c_new = f_s * c + i_s * jnp.tanh(z_raw)
        n_new = f_s * n + i_s
        h_new = jax.nn.sigmoid(o_raw) * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new, h_new), h_new

    xs = jnp.moveaxis(x_gates, 1, 0)
    carry, hs = jax.lax.scan(jax.checkpoint(step), carry, xs)
    return jnp.moveaxis(hs, 0, 1), carry            # (B,S,H,Dh)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def mlstm_block_init(key, cfg: ArchConfig, dtype=L.DEFAULT_DTYPE):
    D = cfg.d_model
    Di = 2 * D
    H = cfg.n_heads
    ks = jax.random.split(key, 8)
    return {
        "norm": L.norm_init(D, cfg.norm),
        "w_up": L.dense_init(ks[0], D, Di, dtype=dtype),
        "w_z": L.dense_init(ks[1], D, Di, dtype=dtype),
        "conv": (jax.random.normal(ks[2], (Di, 4), jnp.float32)
                 / 2.0).astype(dtype),
        "wq": L.dense_init(ks[3], Di, Di, dtype=dtype),
        "wk": L.dense_init(ks[4], Di, Di, dtype=dtype),
        "wv": L.dense_init(ks[5], Di, Di, dtype=dtype),
        "w_if": L.dense_init(ks[6], Di, 2 * H, dtype=jnp.float32,
                             scale=0.01),
        "if_bias": jnp.concatenate([jnp.zeros((H,)),
                                    jnp.linspace(3.0, 6.0, H)]
                                   ).astype(jnp.float32),
        "onorm": {"w": jnp.ones((Di,), jnp.float32)},
        "w_down": L.dense_init(ks[7], Di, D, dtype=dtype),
    }


def mlstm_block_axes(cfg: ArchConfig):
    return {
        "norm": _norm_axes(cfg),
        "w_up": ("embed", "heads"), "w_z": ("embed", "heads"),
        "conv": ("heads", None),
        "wq": ("heads", None), "wk": ("heads", None), "wv": ("heads", None),
        "w_if": ("heads", None), "if_bias": (None,),
        "onorm": {"w": ("heads",)},
        "w_down": ("heads", "embed"),
    }


def _mlstm_qkvg(x, p, cfg: ArchConfig, conv_state=None):
    B, S, D = x.shape
    H = cfg.n_heads
    Di = 2 * D
    Dh = Di // H
    xu = jnp.einsum("bsd,de->bse", x, p["w_up"])
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xc = jax.nn.silu(L.causal_conv1d(xu, p["conv"], state=conv_state))
    q = jnp.einsum("bse,ef->bsf", xc, p["wq"]).reshape(B, S, H, Dh)
    k = jnp.einsum("bse,ef->bsf", xc, p["wk"]).reshape(B, S, H, Dh)
    v = jnp.einsum("bse,ef->bsf", xu, p["wv"]).reshape(B, S, H, Dh)
    gates = (jnp.einsum("bse,eg->bsg", xu.astype(jnp.float32),
                        p["w_if"]) + p["if_bias"])
    i_raw, f_raw = gates[..., :H], gates[..., H:]
    return xu, z, q, k, v, i_raw, f_raw


@jax.named_scope("mlstm")
def mlstm_block_apply(x, p, cfg: ArchConfig, *, chunk: int = 256,
                      use_kernel: bool = False):
    B, S, D = x.shape
    h = L.norm_apply(x, p["norm"], cfg.norm, cfg.norm_eps)
    xu, z, q, k, v, i_raw, f_raw = _mlstm_qkvg(h, p, cfg)
    if use_kernel:
        from repro.kernels.mlstm import ops as mops
        out, _ = mops.mlstm(q, k, v, i_raw, f_raw, chunk=min(chunk, S))
    else:
        out, _ = mlstm_chunked(q, k, v, i_raw, f_raw, chunk=min(chunk, S))
    out = out.reshape(B, S, -1)
    out = L.rmsnorm(out, p["onorm"]["w"], cfg.norm_eps)
    out = out * jax.nn.silu(z.astype(jnp.float32)).astype(out.dtype)
    return x + jnp.einsum("bse,ed->bsd", out, p["w_down"])


def slstm_block_init(key, cfg: ArchConfig, dtype=L.DEFAULT_DTYPE):
    D = cfg.d_model
    H = cfg.n_heads
    Dh = D // H
    ks = jax.random.split(key, 3)
    return {
        "norm": L.norm_init(D, cfg.norm),
        "w_in": L.dense_init(ks[0], D, 4 * D, dtype=dtype),
        "gate_bias": jnp.concatenate(
            [jnp.zeros((D,)), jnp.broadcast_to(
                jnp.linspace(3.0, 6.0, H)[:, None], (H, Dh)).reshape(-1),
             jnp.zeros((2 * D,))]).astype(jnp.float32),
        "r_w": (jax.random.normal(ks[1], (H, 4, Dh, Dh), jnp.float32)
                * 0.01),
        "onorm": {"w": jnp.ones((D,), jnp.float32)},
        "w_out": L.dense_init(ks[2], D, D, dtype=dtype),
    }


def slstm_block_axes(cfg: ArchConfig):
    return {
        "norm": _norm_axes(cfg),
        "w_in": ("embed", "heads"), "gate_bias": (None,),
        "r_w": ("heads", None, None, None),
        "onorm": {"w": ("heads",)},
        "w_out": ("heads", "embed"),
    }


def _slstm_gates(x, p, cfg):
    B, S, D = x.shape
    H = cfg.n_heads
    Dh = D // H
    g = (jnp.einsum("bsd,dg->bsg", x, p["w_in"]).astype(jnp.float32)
         + p["gate_bias"])
    # layout: (i all heads, f all heads, z, o)
    return g.reshape(B, S, 4, H, Dh).transpose(0, 1, 3, 2, 4)  # (B,S,H,4,Dh)


@jax.named_scope("slstm")
def slstm_block_apply(x, p, cfg: ArchConfig, carry=None):
    B, S, D = x.shape
    H = cfg.n_heads
    Dh = D // H
    h = L.norm_apply(x, p["norm"], cfg.norm, cfg.norm_eps)
    xg = _slstm_gates(h, p, cfg)
    if carry is None:
        zero = jnp.zeros((B, H, Dh), jnp.float32)
        carry = (zero, zero, jnp.full((B, H, Dh), -1e30, jnp.float32), zero)
    hs, carry = slstm_scan(xg, p["r_w"], carry)
    hs = hs.reshape(B, S, D).astype(x.dtype)
    hs = L.rmsnorm(hs, p["onorm"]["w"], cfg.norm_eps)
    return x + jnp.einsum("bsd,de->bse", hs, p["w_out"]), carry


# ---------------------------------------------------------------------------
# the model: superblocks of (slstm_every-1 mLSTM + 1 sLSTM)
# ---------------------------------------------------------------------------

class XLSTMLM:
    def __init__(self, cfg: ArchConfig, *, remat: bool = True):
        self.cfg = cfg
        self.remat = remat
        se = cfg.slstm_every
        self.n_super = cfg.n_layers // se if se else 0
        self.n_m_per_super = (se - 1) if se else 0
        self.n_tail = cfg.n_layers - (self.n_super * se if se else 0)

    def init(self, rng):
        cfg = self.cfg
        ke, km, kt = jax.random.split(rng, 3)
        p: Dict[str, Any] = {
            "embed": L.embed_init(ke, cfg.vocab_size, cfg.d_model),
            "final_norm": L.norm_init(cfg.d_model, cfg.norm),
        }
        if self.n_super:
            def super_init(k):
                k1, k2 = jax.random.split(k)
                return {
                    "mlstm": jax.vmap(lambda kk: mlstm_block_init(kk, cfg))(
                        jax.random.split(k1, self.n_m_per_super)),
                    "slstm": slstm_block_init(k2, cfg),
                }
            p["blocks"] = jax.vmap(super_init)(
                jax.random.split(km, self.n_super))
        if self.n_tail:
            p["tail"] = jax.vmap(lambda kk: mlstm_block_init(kk, cfg))(
                jax.random.split(kt, self.n_tail))
        return p

    def param_logical_axes(self):
        cfg = self.cfg
        p = {"embed": ("vocab", "embed"), "final_norm": _norm_axes(cfg)}
        if self.n_super:
            p["blocks"] = {
                "mlstm": jax.tree.map(
                    lambda ax: (None, None) + ax, mlstm_block_axes(cfg),
                    is_leaf=lambda v: isinstance(v, tuple)),
                "slstm": _stacked(slstm_block_axes(cfg)),
            }
        if self.n_tail:
            p["tail"] = _stacked(mlstm_block_axes(cfg))
        return p

    def forward_logits(self, params, batch):
        cfg = self.cfg
        with jax.named_scope("head"):
            x = params["embed"][batch["tokens"]]
        x = shard(x, "batch", None, None)

        def inner(x, bp):
            return mlstm_block_apply(x, bp, cfg), None

        # the scans over mLSTM blocks are in the block's scope too: their
        # slicing and stacking of per-block state is mLSTM work
        def super_body(x, sp):
            with jax.named_scope("mlstm"):
                x, _ = jax.lax.scan(inner, x, sp["mlstm"])
            x, _ = slstm_block_apply(x, sp["slstm"], cfg)
            return x, None

        if self.n_super:
            f = jax.checkpoint(super_body) if self.remat else super_body
            x, _ = jax.lax.scan(f, x, params["blocks"])
        if self.n_tail:
            g = jax.checkpoint(inner) if self.remat else inner
            with jax.named_scope("mlstm"):
                x, _ = jax.lax.scan(g, x, params["tail"])
        with jax.named_scope("head"):
            x = L.norm_apply(x, params["final_norm"], cfg.norm,
                             cfg.norm_eps)
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        return shard(logits, "batch", None, "vocab"), jnp.zeros(
            (), jnp.float32)

    def loss(self, params, batch):
        logits, aux = self.forward_logits(params, batch)
        with jax.named_scope("head"):
            nll, zl = L.softmax_xent(logits, batch["targets"])
        return nll + zl, {"nll": nll, "z_loss": zl, "aux": aux}

    # ------------------------------------------------------------- decode
    def init_cache(self, batch_size: int, seq_len: int):
        cfg = self.cfg
        D = cfg.d_model
        H = cfg.n_heads
        Di = 2 * D
        Dh = Di // H
        Dh_s = D // H

        def m_cache(n):
            return {
                "conv": jnp.zeros((n, batch_size, 3, Di), L.DEFAULT_DTYPE),
                "C": jnp.zeros((n, batch_size, H, Dh, Dh), jnp.float32),
                "n": jnp.zeros((n, batch_size, H, Dh), jnp.float32),
                "m": jnp.full((n, batch_size, H), -1e30, jnp.float32),
            }

        cache: Dict[str, Any] = {}
        if self.n_super:
            cache["mlstm"] = jax.tree.map(
                lambda a: a.reshape((self.n_super, self.n_m_per_super)
                                    + a.shape[1:]),
                m_cache(self.n_super * self.n_m_per_super))
            zero = jnp.zeros((self.n_super, batch_size, H, Dh_s),
                             jnp.float32)
            cache["slstm"] = {
                "c": zero, "n": zero,
                "m": jnp.full_like(zero, -1e30), "h": zero,
            }
        if self.n_tail:
            cache["tail"] = m_cache(self.n_tail)
        return cache

    def cache_logical_axes(self):
        m_ax = {"conv": (None, "kv_batch", None, "heads"),
                "C": (None, "kv_batch", "heads", None, None),
                "n": (None, "kv_batch", "heads", None),
                "m": (None, "kv_batch", "heads")}
        axes: Dict[str, Any] = {}
        if self.n_super:
            axes["mlstm"] = jax.tree.map(
                lambda ax: (None,) + ax, m_ax,
                is_leaf=lambda v: isinstance(v, tuple))
            s_ax = (None, "kv_batch", "heads", None)
            axes["slstm"] = {"c": s_ax, "n": s_ax, "m": s_ax, "h": s_ax}
        if self.n_tail:
            axes["tail"] = m_ax
        return axes

    def _mlstm_decode(self, x, bp, c):
        cfg = self.cfg
        B = x.shape[0]
        h = L.norm_apply(x, bp["norm"], cfg.norm, cfg.norm_eps)
        xu, z, q, k, v, i_raw, f_raw = _mlstm_qkvg(
            h, bp, cfg, conv_state=c["conv"])
        new_conv = jnp.concatenate(
            [c["conv"][:, 1:], jnp.einsum(
                "bsd,de->bse", h, bp["w_up"]).astype(c["conv"].dtype)],
            axis=1)
        hq, (C, n, m) = mlstm_step(q[:, 0], k[:, 0], v[:, 0],
                                   i_raw[:, 0], f_raw[:, 0],
                                   (c["C"], c["n"], c["m"]))
        out = hq.reshape(B, 1, -1)
        out = L.rmsnorm(out, bp["onorm"]["w"], cfg.norm_eps)
        out = out * jax.nn.silu(z.astype(jnp.float32)).astype(out.dtype)
        x = x + jnp.einsum("bse,ed->bsd", out, bp["w_down"])
        return x, {"conv": new_conv, "C": C, "n": n, "m": m}

    def decode_step(self, params, cache, tokens, pos):
        cfg = self.cfg
        x = params["embed"][tokens]
        new_cache: Dict[str, Any] = {}

        if self.n_super:
            def super_body(x, inp):
                sp, mc, sc = inp

                def inner(x, bp_c):
                    bp, c = bp_c
                    return self._mlstm_decode(x, bp, c)

                x, mc = jax.lax.scan(inner, x, (sp["mlstm"], mc))
                # slstm single step
                h = L.norm_apply(x, sp["slstm"]["norm"], cfg.norm,
                                 cfg.norm_eps)
                xg = _slstm_gates(h, sp["slstm"], cfg)
                hs, (c_, n_, m_, h_) = slstm_scan(
                    xg, sp["slstm"]["r_w"],
                    (sc["c"], sc["n"], sc["m"], sc["h"]))
                hs = hs.reshape(x.shape).astype(x.dtype)
                hs = L.rmsnorm(hs, sp["slstm"]["onorm"]["w"], cfg.norm_eps)
                x = x + jnp.einsum("bsd,de->bse", hs, sp["slstm"]["w_out"])
                return x, (mc, {"c": c_, "n": n_, "m": m_, "h": h_})

            x, (mc, sc) = jax.lax.scan(
                super_body, x,
                (params["blocks"], cache["mlstm"], cache["slstm"]))
            new_cache["mlstm"], new_cache["slstm"] = mc, sc
        if self.n_tail:
            def inner(x, bp_c):
                bp, c = bp_c
                return self._mlstm_decode(x, bp, c)
            x, tc = jax.lax.scan(inner, x, (params["tail"], cache["tail"]))
            new_cache["tail"] = tc
        x = L.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        return logits, new_cache

    def input_specs(self, shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                    "targets": jax.ShapeDtypeStruct((B, S), i32)}
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "pos": jax.ShapeDtypeStruct((), i32)}
