"""FFN variants: dense MLP and a dropless token-choice expert layer.

The expert layer holds ``n_held`` of the routed experts, from index
``first_held`` (all of them by default).  The router keeps its published
width: every token is scored against every expert and routed to its
top-k.  The (token, expert) pairs that land on a held expert are sorted
by expert and run through the grouped matmul (``kernels.grouped_matmul``)
for gate, up and down; each result is added back into its token's row,
weighted by its gate.  No pair is dropped at any skew: the sorted buffer
has a row for every pair, and the kernel visits only the rows routed to
held experts.  Pairs routed to experts held elsewhere add nothing here;
on such a share the gates pass no gradient to the router, which learns
from the balance loss alone.

Under a mesh with an ``expert`` rule the held experts are split over
that axis with ``shard_map``: shard ``i`` runs the same layer on its
slice, the experts from ``first_held + i * count``, and the outputs are
summed with one psum.  Differentiable end to end.

``moe_apply`` runs under the caller's ``mlp`` scope, in the scopes
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine`` and
``moe_shared``, and returns beside the output the layer's ``stats``: the
balance loss ``aux``, ``moe_held_pairs`` (pairs routed to held experts)
and ``moe_max_expert_pairs`` (the largest load on one held expert).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models import layers as L
from repro import parallel as PX
from repro.sharding import batch_axes, current_rules

Stats = Dict[str, jax.Array]
MAX_COUNTER = "moe_max_expert_pairs"


def zero_stats(cfg: ArchConfig) -> Stats:
    """What a layer without experts adds to the model's stats."""
    stats = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.moe is not None:
        stats["moe_held_pairs"] = jnp.zeros((), jnp.int32)
        stats[MAX_COUNTER] = jnp.zeros((), jnp.int32)
    return stats


def merge_stats(a: Stats, b: Stats) -> Stats:
    """Two layers' (or microbatches') stats as one: the largest load is
    the larger, everything else adds up."""
    return {k: jnp.maximum(a[k], b[k]) if k == MAX_COUNTER else a[k] + b[k]
            for k in a}


def moe_init(key, cfg: ArchConfig, dtype=L.DEFAULT_DTYPE):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], d, m.n_experts_padded,
                               dtype=jnp.float32, scale=0.02),
        "w_gate": _expert_init(ks[1], m.n_experts_held, d, m.d_ff, dtype),
        "w_up": _expert_init(ks[2], m.n_experts_held, d, m.d_ff, dtype),
        "w_down": _expert_init(ks[3], m.n_experts_held, m.d_ff, d, dtype),
    }
    if m.n_shared > 0:
        p["shared"] = L.mlp_init(ks[4], d, m.n_shared * m.d_ff, "silu", dtype)
    return p


def _expert_init(key, e, d_in, d_out, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (e, d_in, d_out),
                                        jnp.float32) * scale).astype(dtype)


def moe_logical_axes(cfg: ArchConfig):
    m = cfg.moe
    p = {
        "router": (None, None),
        "w_gate": ("expert", "embed", None),
        "w_up": ("expert", "embed", None),
        "w_down": ("expert", None, "embed"),
    }
    if m.n_shared > 0:
        p["shared"] = L.mlp_logical_axes("silu")
    return p


def _route(x, router_w, m: MoEConfig):
    """Scores of every expert and each token's top-k.  x: (B, S, D).

    Returns the gates and experts of the top-k, (T, k), and the balance
    loss (plus the router z-loss where the config has one)."""
    B, S, D = x.shape
    E = m.n_experts_padded
    logits = jnp.einsum("td,de->te", x.reshape(B * S, D).astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if m.n_routed < E:            # mask padded experts out of routing
        logits = jnp.where(jnp.arange(E)[None, :] < m.n_routed, logits,
                           -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)          # (T, k)

    # balance loss: sum over experts of (share of the top-k picks) x
    # (mean score), both scaled to 1 when uniform, per sequence
    # (``seq_aux``) or over the whole batch
    groups = B if m.seq_aux else 1
    picks = jax.nn.one_hot(top_e.reshape(groups, -1), E, dtype=jnp.float32)
    share = jnp.sum(picks, axis=1) * (E / picks.shape[1])  # (groups, E)
    score = jnp.mean(probs.reshape(groups, -1, E), axis=1)
    aux = jnp.mean(jnp.sum(share * score, axis=-1)) * m.aux_coef
    if m.router_z_coef:
        aux = aux + m.router_z_coef * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return top_p, top_e, aux


@jax.custom_vjp
def _take_rows(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` with inverse ``inv``; the
    gradient is a gather by ``inv``, not a scatter-add."""
    return jnp.take(x, idx, axis=0)


def _take_rows_fwd(x, idx, inv):
    return jnp.take(x, idx, axis=0), (idx, inv)


def _take_rows_bwd(res, g):
    idx, inv = res
    return jnp.take(g, inv, axis=0), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pair_rows(x, order, inv, k):
    """The sorted pairs' rows: row ``order[j] // k`` of ``x`` (T, D) for
    pair j, each token's row once for each of its k picks, without a
    k-fold copy of ``x``.  The gradient gathers by ``inv`` and sums each
    token's k rows: no scatter."""
    return jnp.take(x, order // k, axis=0)


def _pair_rows_fwd(x, order, inv, k):
    return _pair_rows(x, order, inv, k), inv


def _pair_rows_bwd(k, inv, g):
    g = jnp.take(g, inv, axis=0)
    return jnp.sum(g.reshape(-1, k, g.shape[-1]), axis=1), None, None


_pair_rows.defvjp(_pair_rows_fwd, _pair_rows_bwd)


def _held_experts(x2, top_p, top_e, w_gate, w_up, w_down, first):
    """The held experts' part of the layer.  x2: (T, D); top_p, top_e:
    (T, k); w_*: the ``count`` experts from index ``first``.

    Returns (T, D) f32 and the pairs routed to each held expert."""
    T, D = x2.shape
    k = top_e.shape[1]
    count = w_gate.shape[0]
    with jax.named_scope("moe_dispatch"):
        local = top_e - first
        held = (local >= 0) & (local < count)                 # (T, k)
        key = jnp.where(held, local, count).reshape(-1)       # (T*k,)
        # a stable sort by expert, the pairs of other experts last; its
        # inverse by counting (no scatter): a pair's place is the pairs
        # of lower experts plus the earlier pairs of its own
        order = jnp.argsort(key, stable=True)
        onehot = key[:, None] == jnp.arange(count + 1)[None, :]
        sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)      # (count+1,)
        before = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1
        starts = jnp.cumsum(sizes) - sizes
        inv = jnp.sum(jnp.where(onehot, before + starts[None, :], 0),
                      axis=1).astype(order.dtype)
        xs = _pair_rows(x2, order, inv, k)
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(grouped_matmul(xs, w_gate, sizes)) \
            * grouped_matmul(xs, w_up, sizes)
        ys = grouped_matmul(h, w_down, sizes)    # rows past the held: 0
    with jax.named_scope("moe_combine"):
        y = _take_rows(ys, inv, order).reshape(T, k, D)
        gate = jnp.where(held, top_p, 0.0)
        out = jnp.sum(y.astype(jnp.float32) * gate[..., None], axis=1)
    return out, sizes[:count]


def _moe_local(x, router_w, w_gate, w_up, w_down, *, m: MoEConfig,
               first, model_axis: Optional[str]):
    """One shard's expert layer.  x: (B_local, S, D); expert weights:
    the shard's ``count`` experts from index ``first``.  Returns the
    output, the balance loss and the pairs routed to each of its
    experts from its tokens."""
    B, S, D = x.shape
    with jax.named_scope("moe_router"):
        top_p, top_e, aux = _route(x, router_w, m)
        if m.n_experts_held < m.n_routed:
            # one chip's share: the experts held elsewhere add nothing
            # here, so the gates' gradient would see only the held ones
            # and drive the router towards or away from them all; the
            # router learns from the balance loss alone
            top_p = jax.lax.stop_gradient(top_p)
    out, sizes = _held_experts(x.reshape(B * S, D), top_p, top_e,
                               w_gate, w_up, w_down, first)
    if model_axis is not None:
        with jax.named_scope("moe_combine"):
            out = PX.psum(out, model_axis)
    return out.reshape(B, S, D).astype(x.dtype), aux, sizes


def moe_apply(x, p, cfg: ArchConfig) -> Tuple[jax.Array, Stats]:
    """x: (B, S, D) -> (out, stats).  Shared experts added densely."""
    m = cfg.moe
    rules = current_rules()
    model_ax = None
    if rules is not None:
        ma = rules.rules.get("expert")
        if ma is not None:
            model_ax = ma if isinstance(ma, str) else ma[0]

    if model_ax is None:          # single-shard path (one chip, CPU)
        out, aux, sizes = _moe_local(
            x, p["router"], p["w_gate"], p["w_up"], p["w_down"], m=m,
            first=m.first_held, model_axis=None)
        pairs, most = jnp.sum(sizes), jnp.max(sizes)
    else:
        mesh = rules.mesh
        from jax.sharding import PartitionSpec as P
        bspec = rules.rules.get("batch")
        n_model = mesh.shape[model_ax]
        assert m.n_experts_held % n_model == 0, (
            f"experts {m.n_experts_held} must divide model axis {n_model}")
        count = m.n_experts_held // n_model

        def mapped(xl, rw, wg, wu, wd):
            first = m.first_held + PX.axis_index(model_ax) * count
            out, aux, sizes = _moe_local(
                xl, rw, wg, wu, wd, m=m, first=first, model_axis=model_ax)
            # aux is identical across model shards: average it over batch
            # shards; each expert's load sums over them
            for ax in batch_axes(rules):
                aux = PX.pmean(aux, ax)
                sizes = PX.psum(sizes, ax)
            pairs = PX.psum(jnp.sum(sizes), model_ax)
            most = PX.pmax(jnp.max(sizes), model_ax)
            return out, aux, pairs, most

        out, aux, pairs, most = PX.shard_map(
            mapped, mesh=mesh,
            in_specs=(P(bspec, None, None), P(None, None),
                      P(model_ax, None, None), P(model_ax, None, None),
                      P(model_ax, None, None)),
            out_specs=(P(bspec, None, None), P(), P(), P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared > 0:
        with jax.named_scope("moe_shared"):
            out = out + L.mlp_apply(x, p["shared"], "silu")
    return out, {"aux": aux, "moe_held_pairs": pairs, MAX_COUNTER: most}
