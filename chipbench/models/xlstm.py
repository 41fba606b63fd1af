"""Plain float32 reference of the xLSTM language model, its weights and its
model FLOPs.

Written from the xLSTM paper (Beck et al. 2024, arXiv:2405.04517), in
the block layout the program uses, and independent of the program's
code.  The mLSTM cell is computed in the paper's parallel form (its
appendix), in blocks of query rows; ``mlstm_recurrent`` is the paper's
recurrent form, kept for the test that shows both forms agree.  The
sLSTM cell is the paper's recurrent form, with exponential input gate,
log-space stabiliser and normaliser state.

Layout of the model (and of the parameter tree the program trains):

* ``embed`` (V, D), tied to the output head; ``final_norm`` LayerNorm.
* ``blocks``: ``n_layers // slstm_every`` super-blocks of
  ``slstm_every - 1`` mLSTM blocks and one sLSTM block.
* mLSTM block: pre-LayerNorm; up-projections ``w_up`` and ``w_z`` to
  Di = 2D; a causal depthwise convolution of width 4 and SiLU on the
  q/k path; q, k from the convolved stream, v and the (i, f) gate
  pre-activations from the up-projected one; H heads of Di/H; the
  output RMS-normalised over Di, gated by SiLU(z), projected down and
  added to the residual.
* sLSTM block: pre-LayerNorm; one projection to the four gate
  pre-activations (i, f, z, o) of H heads of D/H, each head with its own
  recurrent matrices; the hidden state RMS-normalised, projected and
  added to the residual.

Departures from the paper, all the program's design: the sLSTM block
has no convolution and no gated MLP after it, the mLSTM output norm is
one RMSNorm over Di rather than a per-head group norm, and there is no
learnable skip around the mLSTM cell.  The loss adds a z-loss of
``z_loss * logsumexp**2``.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from chipbench.models import common as C

Params = Dict[str, Any]


def _dims(cfg):
    D = cfg["d_model"]
    H = cfg["n_heads"]
    se = cfg["slstm_every"]
    return D, 2 * D, H, cfg["n_layers"] // se, se - 1


# ---------------------------------------------------------------- weights

def init(key, cfg) -> Params:
    """The benchmark's weights for ``cfg``, in the dtypes they are
    trained in (matrices bf16; gates, biases and norms f32)."""
    D, Di, H, n_super, n_m = _dims(cfg)
    V = cfg["vocab_size"]
    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(key, 16))

    def dense(k, lead, d_in, d_out, scale=None, dtype=bf16):
        s = scale if scale is not None else 1.0 / math.sqrt(d_in)
        return (jax.random.truncated_normal(k, -2.0, 2.0,
                                            lead + (d_in, d_out), f32)
                * s).astype(dtype)

    def ln(lead, d):
        return {"w": jnp.ones(lead + (d,), f32),
                "b": jnp.zeros(lead + (d,), f32)}

    lm = (n_super, n_m)
    mlstm = {
        "norm": ln(lm, D),
        "w_up": dense(next(ks), lm, D, Di),
        "w_z": dense(next(ks), lm, D, Di),
        "conv": (jax.random.normal(next(ks), lm + (Di, 4), f32)
                 / 2.0).astype(bf16),
        "wq": dense(next(ks), lm, Di, Di),
        "wk": dense(next(ks), lm, Di, Di),
        "wv": dense(next(ks), lm, Di, Di),
        "w_if": dense(next(ks), lm, Di, 2 * H, scale=0.01, dtype=f32),
        "if_bias": jnp.broadcast_to(
            jnp.concatenate([jnp.zeros((H,)), jnp.linspace(3.0, 6.0, H)]),
            lm + (2 * H,)).astype(f32),
        "onorm": {"w": jnp.ones(lm + (Di,), f32)},
        "w_down": dense(next(ks), lm, Di, D),
    }
    Dh = D // H
    ls = (n_super,)
    f_bias = jnp.broadcast_to(jnp.linspace(3.0, 6.0, H)[:, None],
                              (H, Dh)).reshape(-1)
    slstm = {
        "norm": ln(ls, D),
        "w_in": dense(next(ks), ls, D, 4 * D),
        "gate_bias": jnp.broadcast_to(
            jnp.concatenate([jnp.zeros((D,)), f_bias, jnp.zeros((2 * D,))]),
            ls + (4 * D,)).astype(f32),
        "r_w": jax.random.normal(next(ks), ls + (H, 4, Dh, Dh), f32) * 0.01,
        "onorm": {"w": jnp.ones(ls + (D,), f32)},
        "w_out": dense(next(ks), ls, D, D),
    }
    return {
        "embed": (jax.random.truncated_normal(next(ks), -2.0, 2.0, (V, D),
                                              f32) * 0.02).astype(bf16),
        "final_norm": ln((), D),
        "blocks": {"mlstm": mlstm, "slstm": slstm},
    }


# ---------------------------------------------------------------- cells

def mlstm_parallel(q, k, v, i_raw, f_raw, *, block: int = 256,
                   mm: Callable = C.mm):
    """Parallel-form mLSTM.  q, k, v: (B, S, H, Dh); gates (B, S, H).

    With F_t the cumulative log forget gate, the decay from s to t is
    log D[t, s] = F_t - F_s + i_s (s <= t), stabilised by its row
    maximum m_t; h_t = sum_s C[t, s] v_s / max(|sum_s C[t, s]|,
    exp(-m_t)) with C = (q k^T / sqrt(Dh)) * exp(log D - m).
    """
    B, S, H, Dh = q.shape
    q = q / math.sqrt(Dh)
    F = jnp.cumsum(jax.nn.log_sigmoid(f_raw), axis=1)       # (B, S, H)
    src = (i_raw - F).transpose(0, 2, 1)                     # (B, H, S)
    block = min(block, S)
    nb = S // block

    @jax.checkpoint
    def rows(args):
        qb, Fb, t0 = args                         # (B,bq,H,Dh), (B,bq,H)
        t = t0 + jnp.arange(block)
        logd = Fb.transpose(0, 2, 1)[..., None] + src[:, :, None, :]
        logd = jnp.where(t[:, None] >= jnp.arange(S)[None, :], logd,
                         -jnp.inf)                             # (B,H,bq,S)
        m = jnp.max(logd, axis=-1)
        c = mm("bqhd,bshd->bhqs", qb, k) * jnp.exp(logd - m[..., None])
        num = mm("bhqs,bshd->bqhd", c, v)
        den = jnp.sum(c, axis=-1).transpose(0, 2, 1)           # (B,bq,H)
        lim = jnp.exp(-m).transpose(0, 2, 1)
        return num / jnp.maximum(jnp.abs(den), lim)[..., None]

    qb = q.reshape(B, nb, block, H, Dh).transpose(1, 0, 2, 3, 4)
    Fb = F.reshape(B, nb, block, H).transpose(1, 0, 2, 3)
    out = jax.lax.map(rows, (qb, Fb, jnp.arange(nb) * block))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dh)


def mlstm_recurrent(q, k, v, i_raw, f_raw):
    """Recurrent-form mLSTM (the paper's equations, token by token)."""
    B, S, H, Dh = q.shape
    q = q / math.sqrt(Dh)

    def step(carry, x):
        Cm, n, m = carry
        qt, kt, vt, it, ft = x
        lf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(lf + m, it)
        ig = jnp.exp(it - m_new)
        fg = jnp.exp(lf + m - m_new)
        Cm = fg[..., None, None] * Cm + ig[..., None, None] * (
            kt[..., :, None] * vt[..., None, :])
        n = fg[..., None] * n + ig[..., None] * kt
        num = jnp.einsum("bhde,bhd->bhe", Cm, qt,
                         precision=jax.lax.Precision.HIGHEST)
        den = jnp.sum(n * qt, axis=-1)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
        return (Cm, n, m_new), h

    init = (jnp.zeros((B, H, Dh, Dh)), jnp.zeros((B, H, Dh)),
            jnp.full((B, H), -jnp.inf))
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, i_raw, f_raw))
    _, hs = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(hs, 0, 1)


def slstm(gates, r_w, *, mm: Callable = C.mm):
    """Recurrent sLSTM.  gates: (B, S, H, 4, Dh) input pre-activations in
    the order (i, f, z, o); r_w: (H, 4, Dh, Dh) recurrent matrices."""
    B, S, H, _, Dh = gates.shape

    def step(carry, g_in):
        c, n, m, h = carry
        g = g_in + mm("bhd,hgde->bhge", h, r_w)
        i, f, z, o = g[:, :, 0], g[:, :, 1], g[:, :, 2], g[:, :, 3]
        lf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(lf + m, i)
        ig = jnp.exp(i - m_new)
        fg = jnp.exp(lf + m - m_new)
        c = fg * c + ig * jnp.tanh(z)
        n = fg * n + ig
        h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1e-6)
        return (c, n, m_new, h), h

    z = jnp.zeros((B, H, Dh))
    init = (z, z, jnp.full((B, H, Dh), -jnp.inf), z)
    _, hs = jax.lax.scan(jax.checkpoint(step), init,
                         jnp.moveaxis(gates, 1, 0))
    return jnp.moveaxis(hs, 0, 1)


# ---------------------------------------------------------------- blocks

def causal_conv(x, w):
    """Depthwise causal convolution.  x: (B, S, C); w: (C, K)."""
    K = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + x.shape[1]] * w[:, j] for j in range(K))


def mlstm_block(x, p, cfg, mm):
    B, S, D = x.shape
    H = cfg["n_heads"]
    Di = 2 * D
    h = C.layernorm(x, p["norm"], cfg["norm_eps"])
    xu = mm("bsd,de->bse", h, p["w_up"])
    z = mm("bsd,de->bse", h, p["w_z"])
    xc = jax.nn.silu(causal_conv(xu, p["conv"]))
    q = mm("bse,ef->bsf", xc, p["wq"]).reshape(B, S, H, Di // H)
    k = mm("bse,ef->bsf", xc, p["wk"]).reshape(B, S, H, Di // H)
    v = mm("bse,ef->bsf", xu, p["wv"]).reshape(B, S, H, Di // H)
    g = mm("bse,eg->bsg", xu, p["w_if"]) + p["if_bias"]
    out = mlstm_parallel(q, k, v, g[..., :H], g[..., H:], mm=mm)
    out = C.rmsnorm(out.reshape(B, S, Di), p["onorm"]["w"],
                    cfg["norm_eps"]) * jax.nn.silu(z)
    return x + mm("bse,ed->bsd", out, p["w_down"])


def slstm_block(x, p, cfg, mm):
    B, S, D = x.shape
    H = cfg["n_heads"]
    h = C.layernorm(x, p["norm"], cfg["norm_eps"])
    g = mm("bsd,dg->bsg", h, p["w_in"]) + p["gate_bias"]
    g = g.reshape(B, S, 4, H, D // H).transpose(0, 1, 3, 2, 4)
    hs = slstm(g, p["r_w"], mm=mm).reshape(B, S, D)
    hs = C.rmsnorm(hs, p["onorm"]["w"], cfg["norm_eps"])
    return x + mm("bsd,de->bse", hs, p["w_out"])


def loss(params: Params, tokens, targets, cfg, *, mm: Callable = C.mm):
    """Mean next-token cross-entropy plus z-loss, all in float32."""
    x = params["embed"][tokens]
    blocks = params["blocks"]
    for s in range(cfg["n_layers"] // cfg["slstm_every"]):
        for j in range(cfg["slstm_every"] - 1):
            bp = jax.tree.map(lambda a: a[s, j], blocks["mlstm"])
            x = jax.checkpoint(mlstm_block, static_argnums=(2, 3))(
                x, bp, cfg, mm)
        sp = jax.tree.map(lambda a: a[s], blocks["slstm"])
        x = jax.checkpoint(slstm_block, static_argnums=(2, 3))(
            x, sp, cfg, mm)
    x = C.layernorm(x, params["final_norm"], cfg["norm_eps"])
    return C.lm_loss(x, params["embed"].T, targets, cfg["z_loss"], mm=mm)


# ---------------------------------------------------------------- FLOPs

def flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs of one token's forward pass: every matmul, the
    convolution, and the mLSTM and sLSTM recurrences in their recurrent
    form (the least work the cells need).  Backward is twice this."""
    D, Di, H, n_super, n_m = _dims(cfg)
    Dh_m, Dh_s = Di // H, D // H
    V = cfg["vocab_size"]
    m_proj = 2 * D * Di * 2 + 3 * 2 * Di * Di + 2 * Di * 2 * H \
        + 2 * Di * D + 2 * Di * 4
    m_cell = 4 * H * Dh_m * Dh_m + 4 * H * Dh_m
    s_blk = 2 * D * 4 * D + 2 * H * Dh_s * 4 * Dh_s + 2 * D * D
    return (n_super * (n_m * (m_proj + m_cell) + s_blk)
            + 2 * D * V)


def flops_per_step(cfg, batch: int, seq_len: int) -> float:
    """Forward and backward, no recomputation: 3x the forward."""
    return 3.0 * batch * seq_len * flops_per_token(cfg, seq_len)
