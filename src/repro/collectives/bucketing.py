"""Bucketed flat-buffer gradient collectives (the fused hot path).

The hierarchical schedule in :mod:`repro.collectives.hierarchical` keeps
bulk traffic on the fast tier and moves only a 1/F shard across the slow
tier — but applied *per gradient tensor* it launches 3 collectives + a pad
for every leaf, hundreds of tiny latency-bound ops per step on a real
model.  This module fuses that: the f32 gradient pytree is flattened into
a small number of fixed-capacity contiguous f32 *buckets* with a
deterministic leaf->bucket layout (offsets + shape/dtype metadata, so
unflattening is exact), and the hierarchical schedule runs **once per
bucket**:

    reduce_scatter(fast)  ->  psum(slow, optionally int8/bf16)  ->
    all_gather(fast)

Bucket sizes are padded to a multiple of ``align`` (the fast-axis size),
so the reduce-scatter needs no per-tensor padding.  The layout is pure
metadata — planning works on concrete arrays, tracers, or
``jax.eval_shape`` outputs alike, so the train step and the optimizer
state initializer always derive the *same* layout from the same pytree.

Two consumers:

- ``cross_pod_mode="hier_bucketed"``: buckets carry gradients; the full
  mean gradient is re-gathered and a replicated optimizer applies it.
- ``cross_pod_mode="hier_bucketed_zero1"``: the schedule stops after the
  slow hop; each rank's optimizer updates only its bucket *shard*
  (f32 masters live sharded over the fast axis) and the updated *params*
  are all-gathered instead of gradients.

:func:`make_bucket_loss_and_grad` differentiates the microbatch-
accumulation scan with respect to the flat f32 buckets directly, so
gradients accumulate flat (no per-leaf zero tree) and no full-size f32
params *tree* is ever materialized inside the scan — the f32 buffer the
scan holds IS the bucket set being differentiated.  (That flat f32
differentiation buffer itself remains: it is what makes bf16 training
accumulation-invariant.  What ZeRO-1 mode additionally saves is the
replicated f32 optimizer state — masters and moments live 1/F-sharded.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import parallel as PX
from repro.collectives.hierarchical import (fast_reduce_scatter,
                                            slow_mean_shard)

DEFAULT_BUCKET_BYTES = 32 << 20          # 32 MiB of f32 per bucket

# Compile options for a program that runs a bucketed schedule.  The bucket
# plan already sets the size of every gradient collective; XLA's all-reduce
# combiner ("all-reduce-combiner" in the TPU compiler, "cpu-all-reduce-
# combiner" in the CPU one) would merge the independent per-bucket slow
# hops into one tuple all-reduce that waits for every bucket, undoing the
# plan and leaving the overlapped schedule nothing to overlap.
NO_COMBINE_COMPILER_OPTIONS = {
    "xla_disable_hlo_passes": "all-reduce-combiner,cpu-all-reduce-combiner",
}


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside the bucket set."""

    bucket: int                  # bucket index
    offset: int                  # f32-element offset within the bucket
    size: int                    # number of elements
    shape: Tuple[int, ...]
    dtype: Any                   # storage dtype (restored on unflatten)


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Deterministic leaf->bucket placement for one pytree structure.

    ``slots`` follow ``jax.tree.flatten`` leaf order; greedy first-fit in
    that order means the layout is a pure function of (tree structure,
    leaf shapes/dtypes, bucket_bytes, align).
    """

    treedef: Any
    slots: Tuple[LeafSlot, ...]
    bucket_sizes: Tuple[int, ...]        # padded numels, each % align == 0
    align: int

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_sizes)

    def n_elements(self) -> int:
        """Live (un-padded) elements across all buckets."""
        return sum(s.size for s in self.slots)

    def n_padded_elements(self) -> int:
        return sum(self.bucket_sizes)


def _round_up(n: int, align: int) -> int:
    return ((n + align - 1) // align) * align


def plan_buckets(tree, *, bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 align: int = 1) -> BucketLayout:
    """Greedy first-fit bucketing of ``tree``'s leaves into f32 buckets.

    A bucket closes when the next leaf would push it past
    ``bucket_bytes`` worth of f32; a single leaf larger than the capacity
    gets a bucket of its own.  Every bucket is padded up to a multiple of
    ``align`` (pass the fast-axis size so reduce-scatter divides evenly).
    """
    assert bucket_bytes >= 4 and align >= 1
    leaves, treedef = jax.tree.flatten(tree)
    capacity = max(1, bucket_bytes // 4)   # f32 elements per bucket
    slots = []
    bucket_sizes = []
    fill = 0
    for leaf in leaves:
        size = 1
        for d in leaf.shape:
            size *= d
        if fill and fill + size > capacity:
            bucket_sizes.append(_round_up(fill, align))
            fill = 0
        slots.append(LeafSlot(bucket=len(bucket_sizes), offset=fill,
                              size=size, shape=tuple(leaf.shape),
                              dtype=leaf.dtype))
        fill += size
    if fill or not bucket_sizes:
        bucket_sizes.append(_round_up(max(fill, 1), align))
    return BucketLayout(treedef=treedef, slots=tuple(slots),
                        bucket_sizes=tuple(bucket_sizes), align=align)


def flatten_to_buckets(layout: BucketLayout, tree) -> Tuple[jax.Array, ...]:
    """Pack the leaves of ``tree`` into f32 buckets per ``layout``.

    Leaves are cast to f32; padding regions are zero.  Exact inverse of
    :func:`unflatten_from_buckets` on the live regions.
    """
    leaves = jax.tree.leaves(tree)
    assert len(leaves) == len(layout.slots), (
        f"{len(leaves)} leaves vs layout of {len(layout.slots)}")
    buckets = []
    for b, cap in enumerate(layout.bucket_sizes):
        parts = [leaf.reshape(-1).astype(jnp.float32)
                 for leaf, slot in zip(leaves, layout.slots)
                 if slot.bucket == b]
        fill = sum(p.shape[0] for p in parts)
        if fill < cap:
            parts.append(jnp.zeros((cap - fill,), jnp.float32))
        buckets.append(parts[0] if len(parts) == 1
                       else jnp.concatenate(parts))
    return tuple(buckets)


def unflatten_from_buckets(layout: BucketLayout,
                           buckets: Sequence[jax.Array], *,
                           dtype=None):
    """Rebuild the pytree from flat buckets.

    ``dtype=None`` restores each leaf's storage dtype from the layout;
    passing a dtype (e.g. ``jnp.float32`` for gradients) overrides it.
    """
    assert len(buckets) == layout.n_buckets
    leaves = []
    for slot in layout.slots:
        flat = jax.lax.slice(buckets[slot.bucket], (slot.offset,),
                             (slot.offset + slot.size,))
        leaves.append(flat.reshape(slot.shape).astype(
            slot.dtype if dtype is None else dtype))
    return jax.tree.unflatten(layout.treedef, leaves)


# ---------------------------------------------------------------------------
# bucket-resident loss/grad + collectives
# ---------------------------------------------------------------------------

def make_bucket_loss_and_grad(model, layout: BucketLayout, *, accum: int):
    """Accumulated (loss, grad-buckets) differentiating wrt flat buckets.

    The forward unflattens the f32 buckets to storage-dtype leaves (so the
    math matches :func:`repro.train.make_loss_and_grad` bit for bit), but
    the cotangent accumulates directly in bucket form: gradients never
    exist as a per-leaf zero tree and no f32 param *tree* is live during
    the scan — only the flat buckets the caller already holds.
    """

    def fn(param_buckets, batch):
        from repro.train import _split_micro
        micro = _split_micro(batch, accum)

        def bucket_loss(bks, mb):
            params = unflatten_from_buckets(layout, bks)
            return model.loss(params, mb)

        def step(carry, mb):
            loss_sum, gbks = carry
            (loss, _metrics), g = jax.value_and_grad(
                bucket_loss, has_aux=True)(param_buckets, mb)
            gbks = tuple(a + b for a, b in zip(gbks, g))
            return (loss_sum + loss, gbks), None

        zero = tuple(jnp.zeros_like(b) for b in param_buckets)
        (loss_sum, grads), _ = jax.lax.scan(
            step, (jnp.zeros((), jnp.float32), zero), micro)
        inv = 1.0 / accum
        return loss_sum * inv, tuple(g * inv for g in grads)

    return fn


def hier_reduce_bucket_shards(buckets: Sequence[jax.Array], *,
                              fast_axis: Optional[str],
                              slow_axis: Optional[str],
                              compress_bits: int = 0,
                              overlap: bool = False,
                              residuals: Optional[Sequence[jax.Array]]
                              = None):
    """One hierarchical reduce per *bucket* (not per tensor).

    Returns each rank's globally-meaned contiguous shard of every bucket
    (full buckets when ``fast_axis`` is None / size 1).

    ``overlap=True`` restructures the k-bucket sync as a depth-1 software
    pipeline: bucket i+1's fast-axis reduce-scatter is issued *before*
    bucket i's slow hop, so on a backend with asynchronous collectives
    the slow (DCN/NET) hop of every bucket but the last hides under the
    next bucket's fast (ICI/SHM) phase.  An ``optimization_barrier``
    bundles the two in-flight fast shards at each stage boundary so the
    compiler cannot re-serialize the issue order; no slow collective ever
    feeds a barrier, so consecutive buckets' slow collectives stay
    data-independent in the lowered HLO
    (:func:`repro.analysis.hlo.slow_collective_chains` proves this).
    Per-bucket arithmetic is shared with the serial schedule
    (:func:`fast_reduce_scatter` / :func:`slow_mean_shard`), so the
    result is bitwise-identical; with a single bucket, a trivial fast
    axis, or no slow axis the pipeline silently degenerates to the
    serial path.

    ``residuals`` (one per bucket, per-rank shard-shaped, in the same
    units as the reduce-scattered shard) switches the compressed slow
    hop to error feedback; the return value is then
    ``(shards, new_residuals)`` instead of just the shards.
    """
    k = len(buckets)
    nf = PX.axis_size(fast_axis) if fast_axis is not None else 1
    ns = PX.axis_size(slow_axis) if slow_axis is not None else 1
    if residuals is not None and compress_bits != 8:
        raise ValueError(
            "error-feedback residuals require the int8 slow hop "
            f"(compress_bits=8, got {compress_bits}) — without it the "
            "residuals would silently never update")
    res_in = tuple(residuals) if residuals is not None else (None,) * k
    assert len(res_in) == k, (len(res_in), k)

    def slow(shard, res):
        out = slow_mean_shard(shard, fast_axis=fast_axis,
                              slow_axis=slow_axis,
                              compress_bits=compress_bits, residual=res)
        return out if res is not None else (out, None)

    pipelined = overlap and k >= 2 and nf > 1 and ns > 1
    shards, res_out = [], []
    if not pipelined:
        for b, res in zip(buckets, res_in):
            s, r = slow(fast_reduce_scatter(b, fast_axis), res)
            shards.append(s)
            res_out.append(r)
    else:
        cur = fast_reduce_scatter(buckets[0], fast_axis)
        for i in range(k):
            nxt = None
            if i + 1 < k:
                nxt = fast_reduce_scatter(buckets[i + 1], fast_axis)
                # pin the pipeline: bucket i+1's reduce-scatter is
                # bundled with bucket i's shard, so it cannot sink below
                # bucket i's slow hop
                cur, nxt = jax.lax.optimization_barrier((cur, nxt))
            s, r = slow(cur, res_in[i])
            shards.append(s)
            res_out.append(r)
            cur = nxt
    if residuals is not None:
        return tuple(shards), tuple(res_out)
    return tuple(shards)


def all_gather_buckets(shards: Sequence[jax.Array], *,
                       fast_axis: Optional[str]) -> Tuple[jax.Array, ...]:
    """Re-assemble full buckets from per-rank shards (identity when the
    fast axis is absent or trivial)."""
    if fast_axis is None or PX.axis_size(fast_axis) <= 1:
        return tuple(shards)
    return tuple(PX.all_gather_flat(s, fast_axis) for s in shards)


def shard_global_norm(shards: Sequence[jax.Array],
                      fast_axis: Optional[str]) -> jax.Array:
    """Global gradient norm from reduce-scattered bucket shards.

    The shards are already summed over the slow axis (replicated there),
    so one psum over the fast axis completes the global sum of squares.
    Both bucketed train paths use this — the replicated-optimizer mode
    passes it into ``optim.apply`` so the two stay bitwise identical.
    """
    ss = jnp.zeros((), jnp.float32)
    for s in shards:
        ss = ss + jnp.sum(jnp.square(s.astype(jnp.float32)))
    if fast_axis is not None and PX.axis_size(fast_axis) > 1:
        ss = PX.psum(ss, fast_axis)
    return jnp.sqrt(ss)
