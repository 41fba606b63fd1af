"""Plain float32 reference of DeepSeek-V2 (-Lite) on one chip's share
of its expert layers, its weights and its model FLOPs.

Written from the DeepSeek-V2 paper (arXiv:2405.04434) and the model's
``config.json`` (hf:deepseek-ai/DeepSeek-V2-Lite), independent of the
program's code:

* token embedding (V, D), an untied output head (D, V); RMSNorm before
  each sublayer and at the end;
* multi-head latent attention, in its expanded form: q = x W_q (no
  low-rank q), split into 128 "nope" and 64 rotary dims per head; the
  latent c = RMSNorm(x W_dkv) of 512 dims gives k_nope = c W_uk and
  v = c W_uv per head; one rotary key x W_krope of 64 dims is shared by
  all heads.  Rotary embedding pairs dim i with i + 32 (rotate-half; the
  released weights interleave the rotary columns, a fixed permutation of
  W_q and W_krope that random weights need not repeat).  Causal softmax
  at scale ``mscale(40, 0.707)**2 / sqrt(192)``, computed in blocks of
  query rows;
* YaRN: inverse frequencies blended between ``1/theta**(2i/d)`` and that
  over ``factor`` by a linear ramp between the dimensions that turn
  ``beta_fast`` and ``beta_slow`` times over the original 4096
  positions; cos and sin scaled by ``mscale(mscale)/mscale(mscale_all)``;
* the first ``first_dense_layers`` layers end in a SwiGLU MLP; the others
  in the expert layer: router logits x W_r over all ``n_routed`` experts,
  a softmax, the top-k scores as gates (not renormalised, scaled by 1);
  of the routed experts only the ``n_held`` from ``first_held`` are this
  chip's, and each is computed densely over every token with its gate
  (zero where the token did not pick it) as weight; the others add
  nothing.  Where that is a share (fewer held than routed), the gates
  pass no gradient to the router: the experts held elsewhere would add
  theirs, and without them the router learns to send every token
  towards or away from the held ones.  Plus ``n_shared`` shared experts
  as one SwiGLU MLP of ``n_shared * d_ff``;
* the sequence-level balance loss: per sequence, the sum over experts
  of (picks of the expert / (S k / E)) x (its mean score), averaged over
  sequences, times ``aux_coef``;
* the loss: next-token cross-entropy plus ``z_loss * logsumexp**2``, plus
  every layer's balance loss.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.models import common as C

Params = Dict[str, Any]


# ---------------------------------------------------------------- weights

def init(key, cfg) -> Params:
    """The benchmark's weights for ``cfg`` in the dtypes they are trained
    in: matrices bfloat16, norms and the router float32.

    Every matrix is drawn at 1/sqrt(fan-in), the embedding at unit scale
    (PaLM's choice, arXiv:2204.02311, section 2), so that each token's
    own row stays as large as what the sublayers add to the residual
    stream.  At 0.02 it is lost under the first attention layer's mean
    over earlier positions, which the Zipf corpus's most frequent ids
    fill: every token then reaches the router in much the same
    direction and the routing collapses before any step (a held expert
    with two thirds of a layer's tokens)."""
    D, V, H = cfg["d_model"], cfg["vocab_size"], cfg["n_heads"]
    m, a = cfg["moe"], cfg["mla"]
    F, n, E = m["d_ff"], m["n_held"] or m["n_routed"], m["n_routed"]
    Fd, Ls = m["dense_d_ff"], m["first_dense_layers"]
    Lm = cfg["n_layers"] - Ls
    R, nope, rope, vd = (a["kv_lora_rank"], a["qk_nope_dim"],
                         a["qk_rope_dim"], a["v_head_dim"])
    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(key, 32))

    def dense(shape, d_in, dtype=bf16, scale=None):
        s = scale if scale is not None else 1.0 / math.sqrt(d_in)
        return (jax.random.truncated_normal(next(ks), -2.0, 2.0, shape, f32)
                * s).astype(dtype)

    def norm(lead, d):
        return {"w": jnp.ones(lead + (d,), f32)}

    def attn(L):
        return {"wq": dense((L, D, H * (nope + rope)), D),
                "w_dkv": dense((L, D, R), D),
                "w_krope": dense((L, D, rope), D),
                "w_uk": dense((L, R, H * nope), R),
                "w_uv": dense((L, R, H * vd), R),
                "wo": dense((L, H * vd, D), H * vd),
                "kv_norm": norm((L,), R)}

    def mlp(L, f):
        return {"w_up": dense((L, D, f), D), "w_down": dense((L, f, D), f),
                "w_gate": dense((L, D, f), D)}

    def layer(L, ffn):
        return {"attn_norm": norm((L,), D), "attn": attn(L),
                "ffn_norm": norm((L,), D), "ffn": ffn}

    experts = {"router": dense((Lm, D, E), D, dtype=f32),
               "w_gate": dense((Lm, n, D, F), D),
               "w_up": dense((Lm, n, D, F), D),
               "w_down": dense((Lm, n, F, D), F),
               "shared": mlp(Lm, m["n_shared"] * F)}
    return {"embed": dense((V, D), 1),
            "final_norm": norm((), D),
            "lm_head": dense((D, V), D),
            "first": layer(Ls, mlp(Ls, Fd)),
            "blocks": layer(Lm, experts)}


# ---------------------------------------------------------------- YaRN

def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(cfg) -> np.ndarray:
    """Inverse frequencies of the rotary dims, (rope_dim // 2,)."""
    d = cfg["mla"]["qk_rope_dim"]
    base = cfg["rope_theta"]
    y = cfg.get("rope_scaling")
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if not y or y["factor"] <= 1:
        return extra
    n = y["original_max_position_embeddings"]

    def dim_of(turns):      # the dim whose wavelength turns ``turns`` in n
        return d * math.log(n / (turns * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(dim_of(y["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp              # 1: extrapolate, 0: interpolate
    return extra / y["factor"] * (1.0 - keep) + extra * keep


def rope(x, cfg):
    """Rotate-half rotary embedding of x: (B, S, H, rope_dim)."""
    S, half = x.shape[1], x.shape[-1] // 2
    y = cfg.get("rope_scaling")
    ang = np.arange(S)[:, None] * rope_freqs(cfg)[None, :]
    scale = (yarn_mscale(y["factor"], y["mscale"])
             / yarn_mscale(y["factor"], y["mscale_all_dim"])) if y else 1.0
    c = jnp.asarray(np.cos(ang) * scale, jnp.float32)[None, :, None]
    s = jnp.asarray(np.sin(ang) * scale, jnp.float32)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def softmax_scale(cfg) -> float:
    a, y = cfg["mla"], cfg.get("rope_scaling")
    scale = 1.0 / math.sqrt(a["qk_nope_dim"] + a["qk_rope_dim"])
    if y and y.get("mscale_all_dim"):
        scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


# ---------------------------------------------------------------- layers

def attention(q, k, v, scale: float, *, block: int = 256,
              mm: Callable = C.mm):
    """Causal softmax attention, blocks of query rows.  q, k: (B, S, H,
    dk); v: (B, S, H, dv)."""
    B, S, H, dk = q.shape
    block = min(block, S)
    nb = S // block

    @jax.checkpoint
    def rows(args):
        qb, t0 = args
        t = t0 + jnp.arange(block)
        s = mm("bqhd,bshd->bhqs", qb, k) * scale
        s = jnp.where(t[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return mm("bhqs,bshd->bqhd", jax.nn.softmax(s, axis=-1), v)

    qb = q.reshape(B, nb, block, H, dk).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(rows, (qb, jnp.arange(nb) * block))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def mla(x, a, cfg, mm):
    B, S, D = x.shape
    H, m = cfg["n_heads"], cfg["mla"]
    nope, rd, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    q = mm("bsd,dh->bsh", x, a["wq"]).reshape(B, S, H, nope + rd)
    c = C.rmsnorm(mm("bsd,dc->bsc", x, a["w_dkv"]), a["kv_norm"]["w"],
                  cfg["norm_eps"])
    k_nope = mm("bsc,ch->bsh", c, a["w_uk"]).reshape(B, S, H, nope)
    v = mm("bsc,ch->bsh", c, a["w_uv"]).reshape(B, S, H, vd)
    k_rope = rope(mm("bsd,dr->bsr", x, a["w_krope"])[:, :, None, :], cfg)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], axis=-1)
    o = attention(q, k, v, softmax_scale(cfg), mm=mm)
    return mm("bsh,hd->bsd", o.reshape(B, S, H * vd), a["wo"])


def swiglu(x, f, mm):
    g = jax.nn.silu(mm("bsd,df->bsf", x, f["w_gate"])) \
        * mm("bsd,df->bsf", x, f["w_up"])
    return mm("bsf,fd->bsd", g, f["w_down"])



def experts(x, f, cfg, mm):
    """This chip's share of the expert layer and its balance loss."""
    B, S, D = x.shape
    m = cfg["moe"]
    E, k = m["n_routed"], m["top_k"]
    logits = mm("bsd,de->bse", x, f["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                 # (B, S, k)
    if (m["n_held"] or E) < E:         # a share: no gate gradient
        top_p = jax.lax.stop_gradient(top_p)
    picks = jnp.sum(jax.nn.one_hot(top_e, E), axis=(1, 2))  # (B, E)
    aux = jnp.mean(jnp.sum(picks / (S * k / E) * jnp.mean(probs, axis=1),
                           axis=-1)) * m["aux_coef"]
    if m.get("router_z_coef"):
        aux = aux + m["router_z_coef"] * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    @jax.checkpoint
    def one(y, we):                    # one held expert, every token
        w, e = we
        gate = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)
        return y + swiglu(x, w, mm) * gate[..., None], None

    held = {n: f[n] for n in ("w_gate", "w_up", "w_down")}
    ids = m["first_held"] + jnp.arange(f["w_gate"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (held, ids))
    return y + swiglu(x, f["shared"], mm), aux


def layer(x, p, cfg, mm, moe: bool):
    h = C.rmsnorm(x, p["attn_norm"]["w"], cfg["norm_eps"])
    x = x + mla(h, p["attn"], cfg, mm)
    h = C.rmsnorm(x, p["ffn_norm"]["w"], cfg["norm_eps"])
    if moe:
        f, aux = experts(h, p["ffn"], cfg, mm)
    else:
        f, aux = swiglu(h, p["ffn"], mm), jnp.zeros((), jnp.float32)
    return x + f, aux


def loss(params: Params, tokens, targets, cfg, *, mm: Callable = C.mm):
    """Mean next-token cross-entropy plus z-loss plus every layer's
    balance loss, all in float32."""
    x = params["embed"][tokens]
    total_aux = jnp.zeros((), jnp.float32)
    run = jax.checkpoint(layer, static_argnums=(2, 3, 4))
    for stack, moe in (("first", False), ("blocks", True)):
        for i in range(params[stack]["attn_norm"]["w"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], params[stack])
            x, aux = run(x, lp, cfg, mm, moe)
            total_aux = total_aux + aux
    x = C.rmsnorm(x, params["final_norm"]["w"], cfg["norm_eps"])
    return C.lm_loss(x, params["lm_head"], targets, cfg["z_loss"],
                     mm=mm) + total_aux


# ---------------------------------------------------------------- FLOPs

def routed_pairs(cfg, tokens: int) -> float:
    """(token, expert) pairs that land on the held experts, at the
    uniform expectation: each of the top-k picks falls on a held expert
    with probability n_held / n_routed."""
    m = cfg["moe"]
    return tokens * m["top_k"] * (m["n_held"] or m["n_routed"]) \
        / m["n_routed"]


def flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs of one token's forward pass, averaged over a sequence
    of ``seq_len``: MLA's projections and causal core (a token attends
    to (seq_len + 1) / 2 positions on average), the router, the routed
    experts at the uniform expectation of held pairs (the active
    parameters), the shared experts, the dense layers' MLP, and the
    head.  Backward is twice this."""
    D, V, H = cfg["d_model"], cfg["vocab_size"], cfg["n_heads"]
    m, a = cfg["moe"], cfg["mla"]
    R, nope, rd, vd = (a["kv_lora_rank"], a["qk_nope_dim"],
                       a["qk_rope_dim"], a["v_head_dim"])
    proj = 2 * D * (H * (nope + rd) + R + rd) + 2 * R * H * (nope + vd) \
        + 2 * H * vd * D
    core = 2 * H * (nope + rd + vd) * (seq_len + 1) / 2
    unit = 3 * 2 * D                     # a SwiGLU's FLOPs per hidden unit
    routed = routed_pairs(cfg, 1) * unit * m["d_ff"]
    moe = 2 * D * m["n_routed"] + routed + unit * m["n_shared"] * m["d_ff"]
    n_dense = m["first_dense_layers"]
    n_moe = cfg["n_layers"] - n_dense
    return (cfg["n_layers"] * (proj + core) + n_moe * moe
            + n_dense * unit * m["dense_d_ff"] + 2 * D * V)


def flops_per_step(cfg, batch: int, seq_len: int) -> float:
    """Forward and backward, no recomputation: 3x the forward."""
    return 3.0 * batch * seq_len * flops_per_token(cfg, seq_len)



def expert_gmm_work(cfg, held_pairs: float, *, remat: bool,
                    itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of one step's grouped-matmul kernel runs, for
    ``held_pairs`` (token, expert) pairs on held experts summed over the
    step's expert layers: the program's ``moe_held_pairs`` counter, or
    ``routed_pairs`` times the expert layers at the uniform expectation.

    Each expert layer multiplies its P rows three times: by the gate and
    up weights (D -> d_ff) and by the down weights (d_ff -> D).  Each
    product (P, K) x (n_held, K, N) runs forward once, again in the
    backward pass when the layer is rematerialised, and twice backward:
    the rows' gradient (P, N) x (n_held, N, K) and the weights' gradient
    (K, P) x (P, N) per expert.  Bytes count each operand read and each
    result written once, at ``itemsize`` bytes."""
    D, m = cfg["d_model"], cfg["moe"]
    F, n = m["d_ff"], m["n_held"] or m["n_routed"]
    n_moe = cfg["n_layers"] - m["first_dense_layers"]
    P = held_pairs
    fwd = 2 if remat else 1
    flops = nbytes = 0.0
    for K, N in ((D, F), (D, F), (F, D)):
        flops += (fwd + 2) * 2 * P * K * N
        weights = n_moe * n * K * N
        nbytes += fwd * (P * K + weights + P * N)
        nbytes += P * N + weights + P * K            # rows' gradient
        nbytes += P * K + P * N + weights            # weights' gradient
    return {"flops": flops, "bytes": nbytes * itemsize}
