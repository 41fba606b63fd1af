"""Mesh construction and axis bookkeeping helpers.

Pure-jax layer under ``repro.sharding`` / ``repro.launch.mesh``: nothing
here imports model or scheduler code, so SPMD plumbing has no cyclic
dependencies.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

Axes = Union[None, str, Tuple[str, ...]]


def axis_tuple(axes: Axes) -> Tuple[str, ...]:
    """Normalize a logical-rule value (None | str | tuple) to a tuple."""
    if axes is None:
        return ()
    return (axes,) if isinstance(axes, str) else tuple(axes)


def axes_size(mesh: Optional[Mesh], axes: Axes) -> int:
    """Product of mesh extents over ``axes`` (1 for None / no mesh)."""
    if mesh is None or axes is None:
        return 1
    n = 1
    for a in axis_tuple(axes):
        n *= mesh.shape[a]
    return n


def make_device_mesh(shape: Sequence[int],
                     axis_names: Sequence[str],
                     *, devices=None) -> Mesh:
    """The repo's one mesh constructor; every axis is ``AxisType.Auto``.

    ``jax.make_mesh`` alone types axes ``Explicit``, under which the
    logical-rule sharding constraints (``sharding.shard``) are rejected.
    With no ``devices`` the first ``prod(shape)`` devices are laid out in
    ``jax.make_mesh``'s topology-aware order; given ``devices`` (exactly
    ``prod(shape)`` of them) are laid out row-major in the order passed,
    which is what placement policies (``core.aggregation``) rely on.
    """
    shape, axis_names = tuple(shape), tuple(axis_names)
    axis_types = (AxisType.Auto,) * len(axis_names)
    if devices is None:
        return jax.make_mesh(shape, axis_names, axis_types=axis_types)
    devs = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(devs, axis_names, axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The repro's production topology: (16,16) or (2,16,16) with 'pod'
    outermost — the slow-transport axis per ``repro.parallel.transport``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_device_mesh(shape, axes)
