"""The dropless expert layer (``models/ffn.py``) on seeded random weights:
no pair is dropped under full skew, and one chip's share of the experts
is exactly its part of the uncut layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.models import ffn as F
from repro.models import layers as L

D = 32


def _cfg(**moe) -> ArchConfig:
    base = dict(n_routed=8, n_shared=2, top_k=2, d_ff=16, seq_aux=True)
    return ArchConfig(arch_id="moe-tiny", family="moe", n_layers=1,
                      d_model=D, n_heads=4, n_kv_heads=4, d_ff=0,
                      vocab_size=64, moe=MoEConfig(**{**base, **moe}))


def _params(cfg, seed=0):
    p = F.moe_init(jax.random.key(seed), cfg)
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _x(seed=1, shape=(2, 24)):
    return jax.random.normal(jax.random.key(seed), shape + (D,), jnp.float32)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def test_dropless_under_full_skew():
    """Every token routed to the same two experts, 2 and 5, both held:
    each takes all 48 tokens (a capacity-bound layer at 1.25 would keep
    15 of them), and the output is the dense per-token sum."""
    cfg = _cfg(n_shared=0)
    p = _params(cfg)
    router = jnp.zeros((D, 8)).at[0, 2].set(20.0).at[0, 5].set(19.0)
    p = dict(p, router=router)
    x = _x().at[..., 0].set(1.0)
    with jax.default_matmul_precision("highest"):
        out, stats = F.moe_apply(x, p, cfg)
        probs = jax.nn.softmax(x @ router, axis=-1)
        want = sum(probs[..., e:e + 1] * _swiglu(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
            for e in (2, 5))
    assert int(stats["moe_held_pairs"]) == 2 * 24 * 2
    assert int(stats["moe_max_expert_pairs"]) == 2 * 24
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed", [0, 1])
def test_shares_add_up_to_the_uncut_layer(seed):
    """Two chips holding experts 0-3 and 4-7: their routed parts, with
    the shared experts (which every chip computes) counted once, add up
    to the layer that holds all eight; the router and its balance loss
    are the same on every share."""
    full = _cfg()
    p = _params(full, seed)
    x = _x(seed + 10)

    def share(first):
        cfg = dataclasses.replace(full, moe=dataclasses.replace(
            full.moe, n_held=4, first_held=first))
        ps = dict(p, **{k: p[k][first:first + 4]
                        for k in ("w_gate", "w_up", "w_down")})
        return F.moe_apply(x, ps, cfg)

    with jax.default_matmul_precision("highest"):
        want, st_full = F.moe_apply(x, p, full)
        (a, st_a), (b, st_b) = share(0), share(4)
        shared = L.mlp_apply(x, p["shared"], "silu")
    np.testing.assert_allclose(np.asarray(a + b - shared),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    assert float(st_a["aux"]) == float(st_b["aux"]) == float(st_full["aux"])
    assert int(st_a["moe_held_pairs"]) + int(st_b["moe_held_pairs"]) \
        == int(st_full["moe_held_pairs"]) == 2 * 24 * 2


def test_balance_loss_per_sequence():
    """``seq_aux``: per sequence, sum over experts of (picks / (S k / E))
    x (mean score), averaged over sequences, times the coefficient."""
    cfg = _cfg(aux_coef=0.5, router_z_coef=0.0)
    p = _params(cfg)
    x = _x()
    _, stats = F.moe_apply(x, p, cfg)
    with jax.default_matmul_precision("highest"):
        probs = np.asarray(jax.nn.softmax(x @ p["router"], axis=-1))
    top = np.argsort(-probs, axis=-1)[..., :2]
    want = []
    for b in range(2):
        picks = np.bincount(top[b].ravel(), minlength=8) / (24 * 2 / 8)
        want.append(np.sum(picks * probs[b].mean(0)))
    assert float(stats["aux"]) == pytest.approx(0.5 * np.mean(want),
                                                rel=1e-5)


def test_pair_rows_match_a_repeated_gather():
    """The sorted pairs' rows gathered from the tokens' rows, value and
    gradient, against gathering from the k-fold repeated rows."""
    T, k = 12, 3
    x = _x(shape=(T,))
    order = jax.random.permutation(jax.random.key(2), T * k)
    inv = jnp.argsort(order)
    w = jax.random.normal(jax.random.key(3), (T * k, D))

    def mine(x):
        return jnp.sum(F._pair_rows(x, order, inv, k) * w)

    def plain(x):
        return jnp.sum(jnp.repeat(x, k, axis=0)[order] * w)

    np.testing.assert_array_equal(F._pair_rows(x, order, inv, k),
                                  jnp.repeat(x, k, axis=0)[order])
    np.testing.assert_allclose(jax.grad(mine)(x), jax.grad(plain)(x),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_held", [0, 4])
def test_router_learns_from_the_gates_only_when_uncut(n_held):
    """The uncut layer's output moves its router through the gates; a
    share's (4 of 8 experts held) does not, so that the absent experts'
    missing outputs do not steer the router."""
    cfg = _cfg(n_held=n_held)
    p = _params(cfg)

    def out(router):
        return jnp.sum(F.moe_apply(_x(), dict(p, router=router), cfg)[0])

    def aux(router):
        return F.moe_apply(_x(), dict(p, router=router), cfg)[1]["aux"]

    g_out = jax.grad(out)(p["router"])
    assert (float(jnp.max(jnp.abs(g_out))) > 0) is (n_held == 0)
    assert float(jnp.max(jnp.abs(jax.grad(aux)(p["router"])))) > 0
