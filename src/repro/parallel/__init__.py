"""Unified SPMD runtime layer.

Every SPMD primitive the repro uses lives behind this package:

- :mod:`repro.parallel.compat` — ``shard_map`` (the only place allowed
  to touch ``jax.shard_map``) and manual-axis introspection;
- :mod:`repro.parallel.mesh` — mesh construction + axis bookkeeping;
- :mod:`repro.parallel.collectives` — named wrappers for the collectives
  (psum / ppermute / all_gather / ...);
- :mod:`repro.parallel.transport` — the canonical transport tiers (SHM /
  NET / ICI / DCN) shared by the analytic models and the runtime.

Model and runtime modules import from here; none of them may call the raw
jax shard_map entry points or re-declare bandwidth constants.
"""
from repro.parallel.collectives import (all_gather, all_gather_flat,
                                        axis_index, axis_size, pmax, pmean,
                                        ppermute, psum, psum_scatter,
                                        reduce_scatter_flat)
from repro.parallel.compat import manual_axes, shard_map
from repro.parallel.mesh import (axes_size, axis_tuple, make_device_mesh,
                                 make_production_mesh)
from repro.parallel.transport import (AXIS_TIER, TIERS, TransportTier,
                                      fast_slow_axes, is_slow_axis,
                                      tier_for_axis)

__all__ = [
    "shard_map", "manual_axes",
    "axes_size", "axis_tuple", "make_device_mesh", "make_production_mesh",
    "psum", "pmean", "pmax", "ppermute", "all_gather", "psum_scatter",
    "axis_index", "axis_size", "reduce_scatter_flat", "all_gather_flat",
    "TIERS", "AXIS_TIER", "TransportTier", "tier_for_axis", "is_slow_axis",
    "fast_slow_axes",
]
