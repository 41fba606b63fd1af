"""Logical aggregation of leaves into JAX meshes (one-to-many on TPU).

This is the runtime half of the one-to-many model on TPU hardware: a job is
given an arbitrary set of leaves (chips) — possibly non-contiguous, spanning
hosts and pods — and we build a ``jax.sharding.Mesh`` whose device order
implements the paper's *topology-aware placement*: leaves are round-robined
across hosts so the collective-heavy mesh axes land on the fast intra-host/
intra-pod fabric (the SHM analogue) and only the outermost axis crosses the
slow boundary (the NET analogue).
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from repro.core.leaves import TpuLeaf
from repro.parallel.mesh import make_device_mesh


def round_robin_order(leaves: Sequence[TpuLeaf]) -> List[TpuLeaf]:
    """Topology-aware (round-robin across hosts) leaf ordering (§3.2)."""
    by_host = {}
    for leaf in leaves:
        by_host.setdefault((leaf.pod, leaf.host), []).append(leaf)
    for v in by_host.values():
        v.sort(key=lambda l: l.chip)
    hosts = sorted(by_host)
    out: List[TpuLeaf] = []
    cursors = {h: 0 for h in hosts}
    while len(out) < len(leaves):
        progressed = False
        for h in hosts:
            if cursors[h] < len(by_host[h]):
                out.append(by_host[h][cursors[h]])
                cursors[h] += 1
                progressed = True
        assert progressed
    return out


def packed_order(leaves: Sequence[TpuLeaf]) -> List[TpuLeaf]:
    """Naive pack-host-first ordering (the Fig. 9 ablation baseline)."""
    return sorted(leaves, key=lambda l: (l.pod, l.host, l.chip))


def grouped_order(leaves: Sequence[TpuLeaf]) -> List[TpuLeaf]:
    """Fast-axis-contiguous ordering: chips of one host stay adjacent so
    the *innermost* mesh axis is intra-host (used to map 'model' onto the
    fastest links)."""
    return packed_order(leaves)


def choose_leaves(all_leaves: Sequence[TpuLeaf], n: int, *,
                  busy: Optional[set] = None) -> List[TpuLeaf]:
    """Allocate ``n`` idle leaves, spreading across hosts (one-to-many)."""
    busy = busy or set()
    idle = [l for l in all_leaves if l.uuid not in busy]
    if len(idle) < n:
        raise RuntimeError(f"need {n} leaves, only {len(idle)} idle")
    return round_robin_order(idle)[:n]


def leaves_to_mesh(leaves: Sequence[TpuLeaf], shape: Tuple[int, ...],
                   axis_names: Tuple[str, ...], *,
                   devices: Optional[Sequence] = None,
                   order: str = "grouped") -> Mesh:
    """Build a Mesh over the job's leaves.

    ``devices``: the jax devices backing each leaf (same length/order as
    ``leaves``); defaults to ``jax.devices()[:len(leaves)]`` which is only
    meaningful with fake host devices (dry-run) or a real multichip runtime.

    ``order``: 'grouped' keeps hosts contiguous on the innermost axis
    (fast-axis collectives stay intra-host); 'round_robin' spreads them
    (the placement the paper's Fig. 9 *evaluates*, optimal for PCIe-bound
    GPU leaves); 'packed' is the naive baseline.
    """
    assert math.prod(shape) == len(leaves), (shape, len(leaves))
    if order == "round_robin":
        ordered = round_robin_order(leaves)
    elif order == "packed":
        ordered = packed_order(leaves)
    else:
        ordered = grouped_order(leaves)
    if devices is None:
        devices = jax.devices()[:len(leaves)]
    index = {l: i for i, l in enumerate(leaves)}
    return make_device_mesh(shape, axis_names,
                            devices=[devices[index[l]] for l in ordered])
