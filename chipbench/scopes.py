"""Where a traced training run's device time and host time go, by the
program's own names (``repro.tracing``).

Device.  Each op on a chip's ``XLA Ops`` line carries, in the trace's
event metadata, a ``tf_op`` stat: the JAX name stack of the HLO
instruction (``jit(wrapped)/while/body/.../checkpoint/attention/...``).
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
``tf_ops`` reads them from the ``.xplane.pb`` with a small protobuf wire
reader.  ``device_split`` gives each op's own time in a window
(``trace.self_times``) to the innermost scope of ``SCOPES`` in its path,
once ``jvp(``, ``transpose(`` and ``)`` are stripped from each part; an
op under ``rematted_computation`` (the forward pass that ``jax.checkpoint``
runs again in the backward pass) also counts toward recompute.  Scoped,
unscoped and idle time then add up to the window.

Host.  ``host_split`` reduces a ``repro.tracing.Recorder`` over the
window's steps, leaving out the steps the profiler traced: its Python
tracer slows the host, so their host times would read high.
``chipbench.breakdown`` runs a cell and prints both.
"""
from __future__ import annotations

import dataclasses
import os
import struct
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench import trace as T

SCOPES = ("attention", "mlp", "mlstm", "slstm", "head", "optimizer")
RECOMPUTE = "rematted_computation"
TF_OP = "tf_op"


# ------------------------------------------------------------ wire reader

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val = struct.unpack_from("<q", buf, i)[0]
            i += 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 5:
            val = struct.unpack_from("<i", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield field, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_values(entries: List[memoryview]) -> Iterator[memoryview]:
    """The values of a protobuf map field (entry: key 1, value 2)."""
    for e in entries:
        for f, v in _fields(e):
            if f == 2:
                yield v


# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map),
# stat_metadata = 5 (map); XEventMetadata: name = 2, stats = 5;
# XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7 (the id of a stat metadata whose name is
# the string)

def tf_ops(path: str, prefix: str = T.DEVICE_PREFIX
           ) -> Dict[str, Dict[str, str]]:
    """Per device plane: the HLO text of each op (the event name that
    ``trace.load`` keeps) -> its ``tf_op``."""
    if os.path.isdir(path):
        path = T.find_xplane(path)
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                stats.append(v)
        if not name.startswith(prefix):
            continue
        stat_names = {}
        for sm in _map_values(stats):
            d = dict(_fields(sm))
            stat_names[d.get(1, 0)] = _text(d.get(2, b""))
        ops: Dict[str, str] = {}
        for em in _map_values(events):
            op, tf_op = "", ""
            for f, v in _fields(em):
                if f == 2:
                    op = _text(v)
                elif f == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == TF_OP:
                        tf_op = (_text(st[5]) if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if tf_op or op not in ops:
                ops[op] = tf_op
        out[name] = ops
    return out


# ------------------------------------------------------------ device

def scope_of(tf_op: str) -> Tuple[Optional[str], bool]:
    """(innermost scope of ``SCOPES`` in the path or None, whether the
    op is rematerialised).  Merged metadata (``a;b``) reads its first
    path; a trailing ``:<type>`` is dropped."""
    path = tf_op.split(";", 1)[0].rsplit(":", 1)[0]
    parts = [p.replace("transpose(", "").replace("jvp(", "")
             .replace(")", "") for p in path.split("/")]
    scope = next((p for p in reversed(parts) if p in SCOPES), None)
    return scope, RECOMPUTE in parts


UNSCOPED = "unscoped"


@dataclasses.dataclass
class DeviceSplit:
    """Times in ns summed over chips; shares are of the window times the
    chips, so they read as a mean over the chips."""
    window_ns: float
    n_devices: int
    # own time of each op: (its scope or UNSCOPED, op name, tf_op) -> ns
    op_ns: Dict[Tuple[str, str, str], float]
    recompute_ns: float
    idle_ns: float

    def pct(self, ns: float) -> float:
        return 100.0 * ns / (self.window_ns * max(self.n_devices, 1))

    def scope_ns(self) -> Dict[str, float]:
        """Own time under each scope and under none (``UNSCOPED``)."""
        out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
        for (label, _, _), t in self.op_ns.items():
            out[label] += t
        return out

    def shares(self) -> Dict[str, float]:
        """``<scope>_pct`` of every scope, ``unscoped_pct``,
        ``recompute_pct`` and ``idle_pct``; without recompute, which
        overlaps the scopes, they add up to 100.  Empty where no op is
        under any scope: a program without the scopes."""
        by = self.scope_ns()
        if not any(by[k] for k in SCOPES):
            return {}
        out = {f"{k}_pct": self.pct(v) for k, v in by.items()}
        out["recompute_pct"] = self.pct(self.recompute_ns)
        out["idle_pct"] = self.pct(self.idle_ns)
        return out

    def top(self, n: int = 5) -> Dict[str, List[list]]:
        """Per scope (and ``UNSCOPED``), its ``n`` longest ops: name,
        ``tf_op`` and seconds."""
        out: Dict[str, List[list]] = {}
        for (label, op, tf_op), t in sorted(self.op_ns.items(),
                                            key=lambda kv: -kv[1]):
            if len(out.setdefault(label, [])) < n:
                out[label].append([op, tf_op, t * 1e-9])
        return out


def device_split(trace: T.Trace, window: T.Interval,
                 ops: Dict[str, Dict[str, str]]) -> DeviceSplit:
    """Each device op's own time in ``window`` by scope; ``ops`` is
    ``tf_ops`` of the same trace."""
    lo, hi = window
    op_ns: Dict[Tuple[str, str, str], float] = {}
    recompute = idle = 0.0
    for dev in trace.devices:
        meta = ops.get(dev.name, {})
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in dev.ops
                   if e > lo and s < hi]
        idle += (hi - lo) - T.measure([(s, e) for s, e, _ in clipped])
        # index names keep each op's full HLO text through self_times
        for i, t in T.self_times([(s, e, str(i))
                                  for i, (s, e, _) in enumerate(clipped)]):
            name = clipped[int(i)][2]
            tf_op = meta.get(name, "")
            scope, remat = scope_of(tf_op)
            key = (scope or UNSCOPED, T.op_name(name), tf_op)
            op_ns[key] = op_ns.get(key, 0.0) + t
            if remat:
                recompute += t
    return DeviceSplit(hi - lo, len(trace.devices), op_ns, recompute, idle)


def merge(parts: Sequence[DeviceSplit]) -> DeviceSplit:
    """Several windows as one: times add up."""
    op_ns: Dict[Tuple[str, str, str], float] = {}
    for p in parts:
        for k, v in p.op_ns.items():
            op_ns[k] = op_ns.get(k, 0.0) + v
    return DeviceSplit(sum(p.window_ns for p in parts),
                       max((p.n_devices for p in parts), default=0),
                       op_ns, sum(p.recompute_ns for p in parts),
                       sum(p.idle_ns for p in parts))


def idle_by_span(trace: T.Trace, window: T.Interval,
                 prefixes: Tuple[str, ...] = ("train.", "handoff.")
                 ) -> Dict[str, float]:
    """The window's device idle time (ns, summed over chips) by the
    innermost program span on the host line at each gap's midpoint."""
    lo, hi = window
    spans = [(s, e, n) for s, e, n in trace.host
             if n.startswith(prefixes)]
    out: Dict[str, float] = {}
    for dev in trace.devices:
        busy = T.clip([(s, e) for s, e, _ in dev.ops], lo, hi)
        for gs, ge in T.subtract([(lo, hi)], busy):
            name = _innermost(spans, (gs + ge) / 2)
            out[name] = out.get(name, 0.0) + (ge - gs)
    return out


def _innermost(spans: Sequence[Tuple[float, float, str]], t: float) -> str:
    """The shortest of ``spans`` that covers ``t``, else ``host idle``."""
    covering = [(e - s, n) for s, e, n in spans if s <= t <= e]
    return min(covering)[1] if covering else "host idle"


# ------------------------------------------------------------ host

def _mean_ms(ns: Sequence[float]) -> Optional[float]:
    return sum(ns) / len(ns) * 1e-6 if ns else None


def host_split(rec, window: Tuple[float, float],
               traced: Sequence[Tuple[float, float]] = ()
               ) -> Dict[str, Optional[float]]:
    """The recorder's spans and counters over a window of steps.

    ``window`` is (start of step 1, start of step n + 1) and ``traced``
    the profiled stretches, all ``perf_counter`` seconds.  Steps are the
    ``train.step`` spans that start in the window and in no traced
    stretch.  None where nothing was recorded.
    """
    ns = lambda t: int(round(t * 1e9))
    lo, hi = ns(window[0]), ns(window[1])
    cut = [(ns(a), ns(b)) for a, b in traced]
    steps = [s for s in rec.named("train.step")
             if lo <= s.start_ns < hi
             and not any(a <= s.start_ns < b for a, b in cut)]
    if not steps:
        return {}
    waits, host = [], []
    for s in steps:
        kids = {k.name: k for k in rec.children(s)}
        if "train.data_wait" in kids:
            waits.append(kids["train.data_wait"].end_ns
                         - kids["train.data_wait"].start_ns)
        if "train.device_wait" in kids:
            dw = kids["train.device_wait"]
            host.append((s.end_ns - s.start_ns)
                        - (dw.end_ns - dw.start_ns))
    compiles = rec.named("compile")
    inits = rec.named("train.init_state")
    return {
        "data_wait_ms": _mean_ms(waits),
        "host_ms_per_step": _mean_ms(host),
        "window_compiles": sum(1 for c in compiles
                               if lo <= c.end_ns < hi),
        "setup_compile_s": sum(c.end_ns - c.start_ns for c in compiles
                               if c.end_ns < lo) * 1e-9,
        "setup_init_s": sum(s.end_ns - s.start_ns for s in inits
                            if s.end_ns < lo) * 1e-9,
        "steps": len(steps),
    }
