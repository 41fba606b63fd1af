"""Thin, named wrappers over the collective primitives used in the repro.

Model/runtime code calls these instead of ``jax.lax.*`` directly so that

- every collective call site names the same vocabulary the analytic
  bandwidth model uses (``repro.parallel.transport`` classifies the axis),
- a JAX rename (as happened to ``shard_map`` / ``axis_size``) or a second
  backend means touching this module, not six call sites.

All of these are valid only inside a :func:`repro.parallel.shard_map`
body (they act on *manual* mesh axes).
"""
from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax

AxisName = Union[str, Tuple[str, ...], Sequence[str]]

__all__ = ["psum", "pmean", "pmax", "ppermute", "all_gather",
           "psum_scatter", "axis_index", "axis_size",
           "reduce_scatter_flat", "all_gather_flat"]


def psum(x, axes: AxisName):
    """Sum-reduce over one or more manual mesh axes."""
    return jax.lax.psum(x, axes)


def pmean(x, axes: AxisName):
    """Mean-reduce over one or more manual mesh axes."""
    return jax.lax.pmean(x, axes)


def pmax(x, axes: AxisName):
    """Max-reduce over one or more manual mesh axes."""
    return jax.lax.pmax(x, axes)


def ppermute(x, axis: str, perm):
    """Point-to-point shift along ``axis``; ``perm`` is (src, dst) pairs.
    Missing destinations receive zeros (the GPipe bubble semantics)."""
    return jax.lax.ppermute(x, axis, perm)


def all_gather(x, axis: str, *, tiled: bool = False, gather_axis: int = 0):
    """Gather per-shard values along a new (or tiled) leading dimension."""
    return jax.lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def psum_scatter(x, axis: str, *, scatter_dimension: int = 0,
                 tiled: bool = False):
    """Reduce-scatter: sum over ``axis``, each shard keeps its slice."""
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension,
                                tiled=tiled)


def reduce_scatter_flat(x, axis: str):
    """Reduce-scatter a flat buffer: sum over ``axis``, rank ``i`` keeps the
    ``i``-th contiguous 1/n slice.

    ``x`` must be 1-D with length divisible by the axis size (the bucket
    layouts guarantee this via their ``align``).  Inverse of
    :func:`all_gather_flat` up to the reduction.
    """
    n = axis_size(axis)
    shard = jax.lax.psum_scatter(x.reshape(n, -1), axis,
                                 scatter_dimension=0, tiled=False)
    return shard.reshape(-1)


def all_gather_flat(shard, axis: str):
    """Concatenate per-rank flat shards in rank order into one flat buffer
    (the inverse of :func:`reduce_scatter_flat`'s slicing)."""
    full = jax.lax.all_gather(shard, axis, axis=0, tiled=False)
    return full.reshape(-1)


def axis_index(axis: str):
    """This shard's coordinate along a manual mesh axis."""
    return jax.lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Static size of a manual mesh axis."""
    return jax.lax.axis_size(axis)
