"""AdamW with warmup-cosine schedule, global-norm clipping, and ZeRO-style
sharding (optimizer state inherits parameter sharding; with FSDP rules the
states are fully sharded over data x model — ZeRO-3 equivalent).

Implemented from scratch (no optax in this environment).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any
    master: Any                    # f32 master weights (None if disabled)


class BucketedOptState(NamedTuple):
    """ZeRO-1-style optimizer state over flat f32 buckets.

    ``mu``/``nu``/``master`` are tuples of 1-D f32 arrays, one per bucket
    of a :class:`repro.collectives.bucketing.BucketLayout`.  On a mesh
    they are sharded over the fast (data) axis — each rank holds only its
    contiguous 1/F shard of every bucket — and the train step's
    ``hier_bucketed_zero1`` path updates them shard-resident.
    """

    step: jax.Array
    mu: Any                        # Tuple[jax.Array, ...]
    nu: Any
    master: Any                    # f32 masters (always present)


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    use_master: bool = True


def lr_schedule(cfg: AdamWConfig, step) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    prog = (step - cfg.warmup_steps) / jnp.maximum(
        cfg.total_steps - cfg.warmup_steps, 1)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return cfg.peak_lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init(cfg: AdamWConfig, params) -> OptState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    # explicit copy: astype on an f32 param would alias the param buffer
    # and break donation in the jitted step
    master = (jax.tree.map(
        lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params)
        if cfg.use_master else None)
    return OptState(step=jnp.zeros((), jnp.int32), mu=zeros,
                    nu=jax.tree.map(jnp.copy, zeros), master=master)


def init_bucketed(cfg: AdamWConfig, params, layout) -> BucketedOptState:
    """Bucketed (flat f32) state for the shard-resident optimizer mode.

    Returns *full* (unsharded) buckets; callers on a mesh device_put them
    with a fast-axis sharding (``PartitionSpec(fast_axis)``) so each rank
    materializes only its shard.  Masters are mandatory in this mode —
    they are the source of truth the params are re-gathered from.
    """
    from repro.collectives.bucketing import flatten_to_buckets
    assert cfg.use_master, "bucketed ZeRO-1 state requires f32 masters"
    # explicit copy: for an f32 leaf that exactly fills a bucket,
    # flatten_to_buckets' reshape+astype is a no-op alias of the param
    # buffer — donating params and masters to the jitted step would then
    # donate the same buffer twice (same guard as optim.init)
    master = tuple(jnp.array(b, dtype=jnp.float32, copy=True)
                   for b in flatten_to_buckets(layout, params))
    return BucketedOptState(
        step=jnp.zeros((), jnp.int32),
        mu=tuple(jnp.zeros_like(b) for b in master),
        nu=tuple(jnp.zeros_like(b) for b in master),
        master=master)


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _clip_scale(cfg: AdamWConfig, gnorm):
    return jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))


def _adamw_update(cfg: AdamWConfig, g, m, v, base, *, lr, b1c, b2c,
                  scale):
    """One elementwise AdamW update -> (m, v, new_w).

    The single source of the update math: ``apply`` (param tree) and
    ``apply_flat`` (flat bucket shards) both call this, which is what
    makes their bitwise parity — the ``hier_bucketed`` vs
    ``hier_bucketed_zero1`` guarantee — structural rather than a
    copy-paste invariant.
    """
    g = g.astype(jnp.float32) * scale
    m = cfg.b1 * m + (1 - cfg.b1) * g
    v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
    mh = m / b1c
    vh = v / b2c
    new_w = base - lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                         + cfg.weight_decay * base)
    return m, v, new_w


@jax.named_scope("optimizer")
def apply(cfg: AdamWConfig, params, grads, state: OptState, *,
          gnorm=None):
    """One AdamW step.  Returns (new_params, new_state, metrics).

    ``gnorm`` lets callers that already hold a reduced view of the
    gradients (e.g. the bucketed hierarchical paths, which compute the
    norm from reduce-scattered shards) supply the clipping norm instead
    of re-deriving it from the full tree.
    """
    if gnorm is None:
        gnorm = global_norm(grads)
    scale = _clip_scale(cfg, gnorm)
    step = state.step + 1
    # the schedule is 0-based (lr_schedule(0) == 0: warmup ramps from
    # zero), so it is evaluated at the count of *completed* steps; the
    # first update then only seeds the Adam moments instead of taking a
    # half-peak sign-descent step off one batch's gradient
    lr = lr_schedule(cfg, state.step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, w):
        base = w if w is not None else p.astype(jnp.float32)
        m, v, new_w = _adamw_update(cfg, g, m, v, base, lr=lr, b1c=b1c,
                                    b2c=b2c, scale=scale)
        return new_w.astype(p.dtype), m, v, new_w

    if state.master is not None:
        out = jax.tree.map(upd, params, grads, state.mu, state.nu,
                           state.master)
    else:
        out = jax.tree.map(lambda p, g, m, v: upd(p, g, m, v, None),
                           params, grads, state.mu, state.nu)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_mu = jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    new_nu = jax.tree.map(lambda t: t[2], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    new_master = (jax.tree.map(lambda t: t[3], out,
                               is_leaf=lambda t: isinstance(t, tuple))
                  if state.master is not None else None)
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_params, OptState(step, new_mu, new_nu, new_master), metrics


@jax.named_scope("optimizer")
def apply_flat(cfg: AdamWConfig, grads, state: BucketedOptState, *,
               gnorm) -> Tuple[BucketedOptState, Dict[str, jax.Array]]:
    """Shard-resident AdamW over flat f32 bucket (shards).

    ``grads`` is a tuple of flat f32 buffers aligned element-for-element
    with ``state``'s buckets — on a mesh, each rank's reduce-scattered
    shard of the globally meaned gradient.  ``gnorm`` must be the *global*
    norm (see ``bucketing.shard_global_norm``); clipping and the schedule
    are then identical to :func:`apply`, and because every remaining op is
    elementwise the update is bitwise-identical to the replicated path.

    Returns (new_state, metrics); params are the caller's to re-gather
    from ``new_state.master`` (cast to storage dtype on unflatten) — that
    is the whole point: gradients never travel the fast tier twice.
    """
    scale = _clip_scale(cfg, gnorm)
    step = state.step + 1
    lr = lr_schedule(cfg, state.step)     # 0-based, as in apply()
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    new_mu, new_nu, new_master = [], [], []
    for g, m, v, w in zip(grads, state.mu, state.nu, state.master):
        m, v, new_w = _adamw_update(cfg, g, m, v, w, lr=lr, b1c=b1c,
                                    b2c=b2c, scale=scale)
        new_mu.append(m)
        new_nu.append(v)
        new_master.append(new_w)

    new_state = BucketedOptState(step, tuple(new_mu), tuple(new_nu),
                                 tuple(new_master))
    return new_state, {"lr": lr, "grad_norm": gnorm}
