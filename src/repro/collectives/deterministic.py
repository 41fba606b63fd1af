"""Deterministic (mesh-factorization-invariant) bucket reduction.

The hierarchical schedule's floating-point sum *grouping* follows the mesh
factorization: on a (2, 2) pod x data mesh the global mean is
``(g0+g1)+(g2+g3)`` while a (4, 1) or (1, 4) mesh sums linearly — the
results differ at the ulp level, so a training run restored onto a
re-factorized mesh (the elastic repack path) drifts bitwise even though
every rank's local gradient is identical.

This module fixes the associativity instead of the mesh: every rank

1. all-gathers all R = S*F per-rank contributions over (slow, fast) into
   *global pod-major rank order* — the linearization is a property of the
   job, not of the (S, F) factorization;
2. sums them with a fixed pairwise balanced-tree fold
   (:func:`tree_fold_sum`) and divides by R.

The result is bitwise-identical for every (S, F) factorization of the
same R ranks, which is what makes the sharded-checkpoint reshard test
(save on (2,2), restore on (4,1)/(1,4), continue) *bitwise* verifiable —
the property the elastic/repack machinery relies on to prove a
reconfiguration lost nothing.

Cost: the gather moves R/F x the bytes of a reduce-scatter and every rank
transiently holds all R contributions, so this is the *verification /
elasticity* schedule, not the bandwidth-optimal one — the hierarchical
bucketed schedule remains the production path.  With
``compress_bits=8`` each rank int8-quantizes its own full contribution
before the gather (4x fewer bytes on every hop) and, with error
feedback, carries the residual of its *own* contribution — per-global-rank
state that reshards exactly under any re-factorization (unlike the
hierarchical EF residuals, whose shard assignment follows the pod
structure).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import parallel as PX
from repro.collectives.compression import dequantize_int8, quantize_int8

# Buckets in deterministic mode are padded to a multiple of this, so the
# padded bucket sizes — and with them every jnp.sum / fold shape — are
# identical across mesh factorizations whose fast-axis size divides it.
DETERMINISTIC_ALIGN = 64


def det_align(fast_size: int) -> int:
    """Mesh-invariant bucket alignment: lcm(fast, DETERMINISTIC_ALIGN).

    For the power-of-two fast sizes real meshes use this is just
    DETERMINISTIC_ALIGN, making the padded bucket sizes a pure function
    of the leaf shapes — the reshard-on-restore exactness guarantee.
    """
    import math
    f = max(1, int(fast_size))
    return f * DETERMINISTIC_ALIGN // math.gcd(f, DETERMINISTIC_ALIGN)


def gather_rank_rows(x, sync_axes: Sequence[str]) -> List[jax.Array]:
    """All-gather ``x`` over ``sync_axes`` in global pod-major order.

    ``sync_axes`` is (outer, ..., inner) — ("pod", "data") in the train
    step.  Returns the R per-rank values, indexed by global linear rank
    id, independent of how R factors over the axes.  The gather is flat,
    so each rank's value is one contiguous slice: a row of an (R, C)
    stack lies across the TPU's (8, 128) tiles, and cutting rows out of
    it compiles to code that grows with C (about 50 s of compile for
    8 x 32 MiB buckets on a v5e).
    """
    flat = x.reshape(-1)
    for ax in reversed(tuple(sync_axes)):
        if PX.axis_size(ax) > 1:
            flat = PX.all_gather(flat, ax, gather_axis=0, tiled=True)
    n = x.size
    return [flat[i * n:(i + 1) * n].reshape(x.shape)
            for i in range(flat.shape[0] // n)]


def tree_fold_sum(rows: Sequence[jax.Array]) -> jax.Array:
    """Balanced pairwise fold of the rows — a fixed summation tree.

    ``((g0+g1)+(g2+g3))+...``: depends only on the number of
    contributions, never on how the mesh factors them.  Odd tails pass
    through to the next level unchanged.
    """
    rows = list(rows)
    while len(rows) > 1:
        odd = rows[len(rows) - len(rows) % 2:]
        rows = [a + b for a, b in zip(rows[0::2], rows[1::2])] + odd
    return rows[0]


def det_mean(x, sync_axes: Sequence[str]):
    """Mesh-invariant mean of a per-rank value (loss scalars, metrics)."""
    axes = tuple(a for a in sync_axes if a and PX.axis_size(a) > 1)
    if not axes:
        return x
    rows = gather_rank_rows(x, sync_axes)
    return tree_fold_sum(rows) / len(rows)


def det_reduce_bucket_full(buckets: Sequence[jax.Array], *,
                           sync_axes: Sequence[str],
                           compress_bits: int = 0,
                           residuals: Optional[Sequence[jax.Array]] = None
                           ) -> Tuple[Tuple[jax.Array, ...], tuple]:
    """Deterministic global mean of flat f32 buckets.

    Every rank ends up holding the *full* meaned bucket (identical bits on
    every rank and for every mesh factorization).  ``compress_bits``
    compresses each rank's own contribution before the gather (16 = bf16,
    8 = int8 + per-bucket scale); ``residuals`` (int8 only; one per
    bucket, each the size of the rank's full bucket) switches on error
    feedback over the rank's own contribution.  Returns
    ``(full_buckets, new_residuals)`` — residuals are ``()`` when error
    feedback is off.
    """
    if residuals is not None and compress_bits != 8:
        raise ValueError(
            "deterministic error feedback requires the int8 contribution "
            f"(compress_bits=8, got {compress_bits})")
    res_in = tuple(residuals) if residuals is not None else (None,) * len(
        tuple(buckets))
    full, res_out = [], []
    for b, res in zip(buckets, res_in):
        contrib = b.astype(jnp.float32)
        new_res = None
        if res is not None:
            contrib = contrib + res.astype(jnp.float32)
        if compress_bits == 8:
            q, scale = quantize_int8(contrib)
            recon = dequantize_int8(q, scale)
            if res is not None:
                new_res = contrib - recon
            rows = [qr.astype(jnp.float32) * sr for qr, sr in zip(
                gather_rank_rows(q, sync_axes),
                gather_rank_rows(scale, sync_axes))]
        elif compress_bits == 16:
            rows = [r.astype(jnp.float32) for r in gather_rank_rows(
                contrib.astype(jnp.bfloat16), sync_axes)]
        else:
            assert compress_bits == 0, compress_bits
            rows = gather_rank_rows(contrib, sync_axes)
        full.append(tree_fold_sum(rows) / len(rows))
        res_out.append(new_res)
    # seal the reduction: without the barrier XLA's algebraic simplifier
    # may fuse the /R division into downstream elementwise consumers
    # (e.g. the optimizer's clip-scale multiply) with context-dependent
    # rounding — observed as a 1-ulp drift on (4,1) meshes, where the
    # ZeRO-1 shard IS the full bucket and the fusion window is widest.
    # The barrier pins `full` to one self-contained subgraph, so its bits
    # depend only on the gathered contributions, never on the consuming
    # program.
    full = list(jax.lax.optimization_barrier(tuple(full)))
    if residuals is not None:
        return tuple(full), tuple(res_out)
    return tuple(full), ()


def det_fast_shards(full_buckets: Sequence[jax.Array],
                    fast_axis: Optional[str]) -> Tuple[jax.Array, ...]:
    """Each rank's contiguous fast-axis slice of the full meaned buckets.

    The deterministic analogue of the reduce-scattered shard the ZeRO-1
    optimizer consumes; identity when the fast axis is absent/trivial.
    """
    if fast_axis is None or PX.axis_size(fast_axis) <= 1:
        return tuple(full_buckets)
    nf = PX.axis_size(fast_axis)
    idx = PX.axis_index(fast_axis)
    out = []
    for b in full_buckets:
        size = b.shape[0] // nf
        out.append(jax.lax.dynamic_slice(b, (idx * size,), (size,)))
    return tuple(out)


def det_global_norm(full_buckets: Sequence[jax.Array]) -> jax.Array:
    """Global gradient norm from the full meaned buckets.

    Pure local arithmetic on data that is bitwise-identical on every rank
    and across factorizations (same padded shapes via :func:`det_align`),
    so no collective is needed and the result is mesh-invariant — the
    clip scale, and with it the whole optimizer update, stays bitwise
    reproducible under resharding.
    """
    ss = jnp.zeros((), jnp.float32)
    for b in full_buckets:
        ss = ss + jnp.sum(jnp.square(b.astype(jnp.float32)))
    return jnp.sqrt(ss)
