"""Logical-axis sharding rules.

Model code annotates tensors with *logical* axis names ("batch", "embed",
"heads", ...).  A ``MeshRules`` mapping — chosen per mesh — resolves logical
names to physical mesh axes.  Outside a rules context (unit tests on one CPU
device) all annotations are no-ops, so the same model code runs everywhere.

This is the layer that implements Flex-MIG's "logical aggregation" on TPU: a
job's leaves form a mesh, and these rules decide which collective rides the
fast intra-pod axis vs the slow cross-pod axis (SHM vs NET in paper terms).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel import axes_size as _axes_size
from repro.parallel import axis_tuple as _axis_tuple
from repro.parallel import manual_axes as _manual_axes

Axes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Maps logical axis names to physical mesh axis names (or None)."""

    rules: Dict[str, Axes]
    mesh: Optional[Mesh] = None

    def to_pspec(self, logical: Tuple[Optional[str], ...]) -> P:
        phys = []
        for name in logical:
            if name is None:
                phys.append(None)
            else:
                if name not in self.rules:
                    raise KeyError(f"unknown logical axis {name!r}; "
                                   f"known: {sorted(self.rules)}")
                phys.append(self.rules[name])
        return P(*phys)


_current: contextvars.ContextVar[Optional[MeshRules]] = contextvars.ContextVar(
    "mesh_rules", default=None)


def current_rules() -> Optional[MeshRules]:
    return _current.get()


@contextlib.contextmanager
def use_rules(rules: Optional[MeshRules]):
    tok = _current.set(rules)
    try:
        yield rules
    finally:
        _current.reset(tok)


def fit_spec(mesh: Mesh, shape: Tuple[int, ...], spec: P) -> P:
    """``spec`` without the axes whose mesh extent does not divide the
    dim they split (that dim stays replicated)."""
    fixed = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (
            len(shape) - len(spec))):
        n = _axes_size(mesh, axes)
        fixed.append(axes if n == 1 or dim % n == 0 else None)
    return P(*fixed)


def shard(x, *logical: Optional[str]):
    """Annotate ``x`` with a sharding constraint for the active rules.

    Axes whose mesh extent does not divide the tensor dim are dropped
    (e.g. whisper's 6 heads under a 16-way model axis stay replicated),
    as are axes currently mapped manually by an enclosing shard_map.
    """
    rules = _current.get()
    if rules is None or rules.mesh is None:
        return x                  # no mesh: constraints are meaningless
    manual = _manual_axes()

    def keep(ax: Axes) -> Axes:
        if ax is None or not manual:
            return ax
        if isinstance(ax, str):
            return None if ax in manual else ax
        kept = tuple(a for a in ax if a not in manual)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    spec = rules.to_pspec(tuple(logical))
    spec = fit_spec(rules.mesh, x.shape, P(*(keep(ax) for ax in spec)))
    if manual and all(ax is None for ax in spec):
        # every axis is manually mapped by the enclosing shard_map: the
        # constraint is vacuous per-rank, and an all-None constraint would
        # demand a mesh context manager at the call site for no effect
        # (outside shard_map an all-None spec still means "replicate", so
        # it is only skipped in the manual case)
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def pspec(*logical: Optional[str]) -> P:
    rules = _current.get()
    if rules is None:
        return P()
    return rules.to_pspec(tuple(logical))


def named_sharding(mesh: Mesh, rules: MeshRules,
                   logical: Tuple[Optional[str], ...]) -> NamedSharding:
    return NamedSharding(mesh, rules.to_pspec(logical))


def batch_axes(rules: Optional[MeshRules] = None) -> Tuple[str, ...]:
    """Physical axes the batch dim is sharded over (for shard_map specs)."""
    rules = rules or _current.get()
    if rules is None:
        return ()
    return _axis_tuple(rules.rules.get("batch"))


def model_axes(rules: Optional[MeshRules] = None) -> Tuple[str, ...]:
    rules = rules or _current.get()
    if rules is None:
        return ()
    return _axis_tuple(rules.rules.get("expert"))


def grad_sync_axes(mesh: Optional[Mesh]
                   ) -> Tuple[Optional[str], Optional[str]]:
    """(fast_axis, slow_axis) for explicit gradient synchronization.

    The manual (shard_map) gradient-sync modes reduce over the
    data-parallel fast axis and the cross-pod slow axis; a mesh carrying
    any *other* non-trivial axis (tensor/expert parallelism) cannot keep
    params replicated inside a fully-manual step, so it is rejected here
    rather than silently miscomputing.
    """
    if mesh is None:
        return None, None
    names = tuple(mesh.axis_names)
    extra = [a for a in names if a not in ("data", "pod")
             and mesh.shape[a] > 1]
    if extra:
        raise ValueError(
            f"manual gradient-sync modes support (pod, data) meshes only; "
            f"mesh has non-trivial axes {extra!r} (use cross_pod_mode="
            f"'xla' for tensor/expert-parallel meshes)")
    fast = "data" if "data" in names else None
    slow = "pod" if "pod" in names else None
    return fast, slow


# ---------------------------------------------------------------------------
# Standard rule sets
# ---------------------------------------------------------------------------

def make_rules(mesh: Mesh, *, seq_shard: bool = False,
               long_ctx: bool = False, fsdp: bool = True,
               seq_parallel: bool = False) -> MeshRules:
    """Production rules for ("pod","data","model") / ("data","model") meshes.

    - batch       -> all data-parallel axes (pod outermost)
    - embed       -> 'data' (FSDP / ZeRO-3 parameter+optimizer sharding)
    - heads/ff/vocab/expert -> 'model' (tensor / expert parallelism)
    - kv_seq      -> 'model' when seq_shard (sequence-parallel long decode)
    """
    names = tuple(mesh.axis_names)
    dp: Axes
    if "pod" in names:
        dp = ("pod", "data")
    elif "data" in names:
        dp = "data"
    else:
        dp = None
    rules: Dict[str, Axes] = {
        "batch": dp,
        # fsdp=False: ZeRO-1 — params replicated over data, optimizer
        # states still sharded (the dry-run passes a second rules set for
        # the opt-state shardings)
        "embed": "data" if (fsdp and "data" in names) else None,
        "heads": "model" if "model" in names else None,
        "kv_heads": None,          # GQA kv heads often don't divide TP; replicate
        "ff": "model" if "model" in names else None,
        "vocab": "model" if "model" in names else None,
        "expert": "model" if "model" in names else None,
        # seq_parallel: residual-stream carriers sharded over 'model' on
        # the sequence dim between layers (Megatron-SP)
        "seq": ("model" if seq_parallel and "model" in names else None),
        "kv_seq": ("model" if seq_shard and "model" in names else None),
        "kv_batch": dp,
        "state": None,
        "conv": None,
        "norm": None,
        "lora": None,
    }
    if long_ctx:
        # long_500k: global_batch=1 -> batch axes replicated; the KV/state
        # sequence axis carries the parallelism instead (SP decode)
        rules["batch"] = None
        rules["kv_batch"] = None
        seq_axes = tuple(a for a in ("data", "model") if a in names)
        rules["kv_seq"] = seq_axes if seq_axes else None
    return MeshRules(rules=rules, mesh=mesh)


def tree_shardings(mesh: Mesh, rules: MeshRules, shapes_tree, axes_tree):
    """NamedShardings for a pytree given logical axes + shapes.

    Non-dividing axes are dropped per-dim (uneven GSPMD shardings are legal
    but we keep params exactly shardable to make memory analysis exact).
    """
    def one(shape_leaf, axes):
        spec = rules.to_pspec(axes)
        fixed = []
        for dim, ax in zip(shape_leaf.shape, tuple(spec) + (None,) * (
                len(shape_leaf.shape) - len(spec))):
            n = _axes_size(mesh, ax)
            fixed.append(ax if (n > 1 and dim % n == 0) or n == 1
                         else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree.map(one, shapes_tree, axes_tree,
                        is_leaf=lambda v: isinstance(v, tuple) and all(
                            isinstance(x, (str, type(None))) for x in v))


def without_axes(rules: MeshRules, drop: frozenset) -> MeshRules:
    """Rules with some physical axes removed (e.g. inside a shard_map that
    maps those axes manually, constraints must not mention them)."""
    new: Dict[str, Axes] = {}
    for k, ax in rules.rules.items():
        if ax is None:
            new[k] = None
        elif isinstance(ax, str):
            new[k] = None if ax in drop else ax
        else:
            kept = tuple(a for a in ax if a not in drop)
            new[k] = kept if len(kept) > 1 else (kept[0] if kept else None)
    return MeshRules(rules=new, mesh=rules.mesh)


def single_device_rules() -> MeshRules:
    return MeshRules(rules={k: None for k in (
        "batch", "embed", "heads", "kv_heads", "ff", "vocab", "expert",
        "seq", "kv_seq", "kv_batch", "state", "conv", "norm", "lora")})
