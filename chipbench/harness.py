"""What every cell's driver shares: the cell's files found by name, the
program's model carrying the benchmark's weights, step markers, the
traced window, the three-step float32 reference and its comparison.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  Its files:

* ``chipbench/configs/<config>.json``: the model (``arch``, the
  program's ``ArchConfig`` fields), its ``family`` (a reference module
  ``chipbench/models/<family>.py``), source, cuts and loss settings;
* ``chipbench/mixes/<traffic>.json``: the traffic, and ``driver``, the
  module ``chipbench/drivers/<driver>.py`` that runs it;
* ``chipbench/limits/<workload>.json``: the limit of each number that
  decides ``correct``;
* ``chipbench/metrics/<metric>.py``: one per-layer metric each.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_file_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files, for one run."""
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, float]
    seed: int
    seconds: float
    trace: bool
    t_start: float                       # perf_counter at process start

    @property
    def ref_cfg(self) -> Dict[str, Any]:
        """What the reference module reads: the arch plus loss settings."""
        return {**self.config["arch"], "z_loss": self.config["z_loss"]}

    @property
    def family(self):
        return importlib.import_module(
            f"chipbench.models.{self.config['family']}")


def load_cell(workload: str, entry: Optional[Dict[str, Any]] = None,
              **run) -> Cell:
    """The cell named ``workload`` in BENCHMARK.json (or described by
    ``entry``, a workload entry of the same form) with its files."""
    if entry is None:
        bench = read_json(ROOT, "BENCHMARK.json")
        found = [w for w in bench["workloads"] if w["name"] == workload]
        if not found:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        entry = found[0]
    return Cell(name=workload, chips=entry["chips"],
                config=read_json(HERE, "configs", entry["config"] + ".json"),
                mix=read_json(HERE, "mixes", entry["traffic"] + ".json"),
                limits=read_json(HERE, "limits", workload + ".json"),
                **run)


# ------------------------------------------------------------------ model

def seed_key(seed: int):
    """A PRNG key from a seed of any size (JAX keeps 32 bits of an int)."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_weights(cell: Cell) -> Callable[[], Any]:
    """One jitted call from the seed to the weights, on the device."""
    import jax
    fam, cfg = cell.family, cell.ref_cfg
    fn = jax.jit(lambda k: fam.init(k, cfg))
    return lambda: fn(seed_key(cell.seed))


class SeededModel:
    """The program's model, initialised with the benchmark's weights.

    ``init`` ignores the key it is given and returns the weights the
    benchmark makes from its seed (fresh arrays on every call, since the
    train step donates them); everything else is the program's model.
    """

    def __init__(self, model, weights: Callable[[], Any]):
        self._model = model
        self._weights = weights

    def init(self, rng):
        return self._weights()

    def __getattr__(self, name):
        return getattr(self._model, name)


def build_model(cell: Cell):
    """The program's model for the cell's config, with the benchmark's
    weights; checks that both describe the same parameter tree."""
    import jax
    from repro.configs.base import ArchConfig
    from repro.models.registry import build_model as program_model
    model = program_model(ArchConfig(**cell.config["arch"]),
                          remat=cell.mix["remat"])
    weights = make_weights(cell)
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(weights)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
            != [(a.shape, a.dtype) for a in jax.tree.leaves(got)]):
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter tree for "
                         f"{cell.config['name']}")
    return SeededModel(model, weights)


def leaf_names(tree) -> List[str]:
    import jax
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree, scale: float = 1.0) -> np.ndarray:
    """Per-leaf float32 L2 norms of ``scale * tree``."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        scale * a.astype(jnp.float32)))) for a in jax.tree.leaves(t)])
    return np.asarray(jax.device_get(norms(tree)), np.float64)


def host_deltas(new, old) -> List[np.ndarray]:
    """Per leaf, ``new - old`` in float32 on the host, one leaf at a time
    so that the device holds one difference at most."""
    import jax
    import jax.numpy as jnp
    diff = jax.jit(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32))
    return [np.asarray(jax.device_get(diff(a, b)))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def free(tree) -> None:
    import jax
    for a in jax.tree.leaves(tree):
        if hasattr(a, "delete") and not a.is_deleted():
            a.delete()


# ------------------------------------------------------------------ marks

class Marks:
    """Host-clock step boundaries, each also written into the profiler's
    trace as a ``chipbench.mark <i>`` annotation.  ``trace_spans`` lists
    (first, last) mark indices between which the profiler records."""

    def __init__(self, trace_dir: Optional[str] = None,
                 trace_spans: Sequence = ()):
        self.times: List[float] = []
        self.trace_dir = trace_dir
        self.trace_spans = list(trace_spans)
        self.traces: List[Dict[str, Any]] = []

    def mark(self) -> None:
        import jax
        i = len(self.times)
        if any(i == last for _, last in self.trace_spans):
            with jax.profiler.TraceAnnotation(f"chipbench.mark {i}"):
                pass
            jax.profiler.stop_trace()
        for first, last in self.trace_spans:
            if i == first:
                d = os.path.join(self.trace_dir, f"span{first}")
                jax.profiler.start_trace(d)
                self.traces.append({"dir": d, "first": first, "last": last})
        self.times.append(time.perf_counter())
        if self.trace_dir:
            with jax.profiler.TraceAnnotation(f"chipbench.mark {i}"):
                pass

    def intervals(self) -> np.ndarray:
        return np.diff(np.asarray(self.times))


def reduce_traces(marks: Marks):
    """One trace.Reduction per traced span, the window being the span
    from its first marker to its last."""
    from chipbench import trace as T
    out = []
    for t in marks.traces:
        tr = T.load(t["dir"])
        out.append(T.reduce(tr, T.mark_window(tr, t["first"], t["last"])))
    return out


# ------------------------------------------------------------------ device

def device_info(n: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()[:n]
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


# ------------------------------------------------------------------ reference

@dataclasses.dataclass
class Readings:
    """The three steps a comparison is made on."""
    losses: List[float]
    grad_norms: np.ndarray          # per leaf: first (clipped) gradient
    update: List[np.ndarray]        # per leaf: master change after 3 steps
    # the reference's only: per leaf, the elements whose first gradient
    # is not nought to rounding (see ``compare``)
    moved: Optional[List[np.ndarray]] = None


def lr_at(ocfg: Dict[str, Any], t: int) -> float:
    """Learning rate of the update made after ``t`` completed steps:
    linear warm-up from 0, then cosine to ``min_lr_frac``."""
    w, n = ocfg["warmup_steps"], ocfg["total_steps"]
    if t < w:
        return ocfg["peak_lr"] * t / max(w, 1)
    prog = min(max((t - w) / max(n - w, 1), 0.0), 1.0)
    f = ocfg["min_lr_frac"]
    return ocfg["peak_lr"] * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi
                                                                * prog)))


def reference_step(cell: Cell, matmul: str = "float32"):
    """One AdamW step of the plain float32 reference, jitted:
    ``(params, m, v, batch, t, lr) -> (params, m, v, loss, grad_norms)``
    with ``grad_norms`` the per-leaf norms of the clipped gradient.
    ``params`` are the float32 masters; the forward pass reads each leaf
    rounded to the type the configuration states for it (the type
    ``init`` makes it in), as a model whose bfloat16 weights are copies
    of float32 masters does, and its gradient is taken at those rounded
    weights.  ``matmul='fp8'`` is the control."""
    import jax
    import jax.numpy as jnp
    from chipbench.models import common as C

    fam, cfg, oc = cell.family, cell.ref_cfg, cell.mix["optimizer"]
    mm = C.MATMULS[matmul]
    stated = [a.dtype for a in jax.tree.leaves(
        jax.eval_shape(lambda: fam.init(jax.random.key(0), cfg)))]

    def rounded(w, d):
        # reduce_precision, not a cast there and back, which XLA may drop
        # as excess precision
        if d == jnp.float32:
            return w
        f = jnp.finfo(d)
        return jax.lax.reduce_precision(w, exponent_bits=f.nexp,
                                        mantissa_bits=f.nmant)

    def as_stated(p):
        leaves, tree = jax.tree.flatten(p)
        return jax.tree.unflatten(tree, [
            w + jax.lax.stop_gradient(rounded(w, d) - w)
            for w, d in zip(leaves, stated)])

    def loss_fn(p, b):
        return fam.loss(as_stated(p), b["tokens"], b["targets"], cfg, mm=mm)

    def step(p, m, v, b, t, lr):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, oc["clip_norm"] / jnp.maximum(gn, 1e-12)), g)
        b1, b2 = oc["b1"], oc["b2"]
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        p = jax.tree.map(
            lambda w, a, s: w - lr * ((a / c1) / (jnp.sqrt(s / c2)
                                                  + oc["eps"])
                                      + oc["weight_decay"] * w), p, m, v)
        norms = [jnp.sqrt(jnp.sum(jnp.square(x)))
                 for x in jax.tree.leaves(g)]
        return p, m, v, loss, norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def reference_readings(cell: Cell, batches: Sequence[Dict[str, np.ndarray]],
                       *, matmul: str = "float32",
                       rows: Optional[slice] = None,
                       devices: Optional[Sequence] = None) -> Readings:
    """Three AdamW steps of the plain float32 reference from the
    benchmark's weights, on ``batches``.

    ``matmul='fp8'`` is the control.  ``rows`` keeps only those rows of
    each batch (the reference with part of the batch left out).  With
    ``devices`` the batch is split over them, the weights replicated.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    jstep = reference_step(cell, matmul)
    weights = make_weights(cell)
    p = jax.jit(lambda t: jax.tree.map(
        lambda a: a.astype(jnp.float32), t))(weights())
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    put = None
    if devices is not None and len(devices) > 1:
        mesh = Mesh(np.asarray(devices), ("d",))
        put = NamedSharding(mesh, P("d"))
        rep = NamedSharding(mesh, P())
        p, m, v = (jax.device_put(t, rep) for t in (p, m, v))
    oc = cell.mix["optimizer"]
    losses, g1, moved = [], None, None
    for t, b in enumerate(batches[:3]):
        b = {k: np.asarray(a)[rows] if rows is not None else np.asarray(a)
             for k, a in b.items()}
        b = {k: jax.device_put(a, put) if put is not None else jnp.asarray(a)
             for k, a in b.items()}
        p, m, v, loss, norms = jstep(p, m, v, b, jnp.float32(t),
                                     jnp.float32(lr_at(oc, t)))
        losses.append(float(loss))
        if t == 0:
            g1 = np.asarray(jax.device_get(norms), np.float64)
            moved = moved_elements(m, g1, 1.0 - oc["b1"])
    free((m, v))
    p0 = weights()
    upd = host_deltas(p, p0)
    free((p, p0))
    return Readings(losses, g1, upd, moved)


def moved_elements(m, grad_norms: np.ndarray, scale: float
                   ) -> List[np.ndarray]:
    """Per leaf, where the first gradient (``m / scale``, Adam's first
    moment after one step) is at least a thousandth of the median leaf's
    root mean square; elsewhere it is nought to rounding, and Adam moves
    the weight by round-off alone."""
    import jax
    import jax.numpy as jnp
    sizes = np.asarray([a.size for a in jax.tree.leaves(m)], np.float64)
    floor = 1e-3 * float(np.median(grad_norms / np.sqrt(sizes)))
    # the floor is an argument, not a constant, so that every seed runs
    # the same compiled program
    f = jax.jit(lambda t, lo: [jnp.abs(a) >= lo for a in jax.tree.leaves(t)])
    return [np.asarray(x) for x in jax.device_get(
        f(m, jnp.float32(floor * scale)))]


def compare(prog: Readings, ref: Readings, names: Sequence[str]
            ) -> Dict[str, Any]:
    """The numbers that decide ``correct``; a cell's limits file names
    those it compares.

    * ``loss_gap``: the largest relative gap of the three steps' losses;
    * ``grad_gap``: over leaves, the gap between the program's and the
      reference's norm of the first (clipped) gradient, over the larger
      of that leaf's reference norm and the median leaf's;
    * ``grad_gap_median``: the median over leaves of that same gap, for
      a model where one small leaf's gradient is a difference of large
      terms and reads far more than the rest (xLSTM's gate projection);
    * ``update_gap``: the same for the change of the float32 masters
      after three steps.

    Leaves whose reference gradient is below a thousandth of the median
    leaf's are left out of ``grad_gap``.  The update norms count only
    the elements the reference's first gradient moves
    (``moved_elements``): a key's bias has no gradient under the softmax
    in its dimensions that rotary embedding leaves alone, so Adam moves
    them by round-off alone, differently in each precision.
    """
    rel = [abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses)]
    lg = max(rel)
    if not all(math.isfinite(x) for x in prog.losses):
        lg = float("inf")

    def gaps(p, r, keep):
        med = float(np.median(r[keep]))
        rel = np.abs(p - r) / np.maximum(r, med)
        rel = np.where(keep, rel, 0.0)
        return np.where(np.isfinite(p), rel, np.inf)

    def gap(p, r, keep):
        rel = gaps(p, r, keep)
        i = int(np.argmax(rel))
        return float(rel[i]), names[i], float(np.median(rel[keep]))

    keep_g = ref.grad_norms >= 1e-3 * float(np.median(ref.grad_norms))
    gg, gleaf, gmed = gap(prog.grad_norms, ref.grad_norms, keep_g)
    norm = lambda d, k: float(np.sqrt(np.sum(np.square(
        d[k], dtype=np.float64))))
    pu = np.asarray([norm(d, k) for d, k in zip(prog.update, ref.moved)])
    ru = np.asarray([norm(d, k) for d, k in zip(ref.update, ref.moved)])
    ug, uleaf, _ = gap(pu, ru, np.asarray([k.any() for k in ref.moved]))
    unmoved = {n: float(1.0 - k.mean()) for n, k in zip(names, ref.moved)
               if k.mean() < 0.99}
    # per leaf: the program's and the reference's first-gradient norm and
    # update norm, from which every number above is made
    per_leaf = {n: [float(a), float(b), float(c), float(d)]
                for n, a, b, c, d in zip(names, prog.grad_norms,
                                         ref.grad_norms, pu, ru)}
    return {"loss_gap": lg, "grad_gap": gg, "grad_gap_median": gmed,
            "update_gap": ug,
            "loss_gaps_by_step": rel,
            "worst_grad_leaf": gleaf, "worst_update_leaf": uleaf,
            "left_out_leaves": [n for n, k in zip(names, keep_g) if not k],
            "unmoved_share": unmoved, "leaf_norms": per_leaf}


def checks_line(numbers: Dict[str, float], limits: Dict[str, float]
                ) -> Dict[str, Dict[str, float]]:
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def all_within(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def window_steps(seconds: float, step_s: float, *, least: int = 2) -> int:
    """Steps that fill ``seconds`` at ``step_s`` each."""
    return max(least, int(math.ceil(seconds / max(step_s, 1e-3))))
