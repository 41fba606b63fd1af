"""SPMD primitives over ``jax.shard_map``.

Everything in this repro goes through :func:`shard_map` below:

    shard_map(f, mesh=mesh, in_specs=..., out_specs=...,
              check_vma=False, axis_names={"pod"})
"""
from __future__ import annotations

from typing import Callable, Optional, Set

import jax
from jax.sharding import AxisType

__all__ = ["shard_map", "manual_axes"]


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: bool = True,
              axis_names: Optional[Set[str]] = None) -> Callable:
    """Map ``f`` over shards of a mesh.

    ``axis_names``: mesh axes mapped *manually* inside ``f`` (the rest
    stay automatic / visible to the partitioner).  ``None`` means all
    mesh axes are manual, matching the upstream default.
    """
    kw: dict = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def manual_axes() -> frozenset:
    """Mesh axes that are Manual at the current trace point (i.e. we are
    inside a shard_map mapping them) — sharding constraints must not
    mention them."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return frozenset()
    return frozenset(n for n, t in zip(am.axis_names, am.axis_types)
                     if t == AxisType.Manual)
