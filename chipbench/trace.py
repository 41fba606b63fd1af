"""Reduce a JAX profiler trace to device busy time, exposed collective time,
top device ops and the longest idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU
each chip is a plane ``/device:TPU:<i>`` whose ``XLA Ops`` line holds
every operation the chip ran, one after the other, each named by its HLO
text (``%fusion.3 = bf16[...] fusion(...)``).  The host's ``python`` line
holds the harness's step markers (``chipbench.mark <i>``, a
``jax.profiler.TraceAnnotation``) and what the host thread was doing.

A window is the host-clock span between two markers.  Within it:

* busy: the union of op intervals on a device, clipped to the window;
* exposed collective time: the part of the union of collective ops that
  no other op on the same line covers (the ``XLA Ops`` line runs one op
  at a time, so a collective there is time the chip waits on the
  interconnect; asynchronous transfers run on ``Async XLA Ops`` and are
  not counted);
* idle gaps: stretches of the window with no op, named by the innermost
  host event running at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

MARK = "chipbench.mark"
OPS_LINE = "XLA Ops"
HOST_LINE = "python"
DEVICE_PREFIX = "/device:TPU:"
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all", "collective-broadcast",
                  "ragged-all-to-all")

Interval = Tuple[float, float]          # (start_ns, end_ns)

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_name(event_name: str) -> str:
    """The HLO instruction name: ``%fusion.3 = ...`` -> ``fusion.3``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_kind(event_name: str) -> str:
    """The HLO opcode of an op event (``fusion``, ``all-reduce-start``)."""
    _, _, rest = event_name.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return m.group(1) if m else op_name(event_name)


def is_collective(event_name: str) -> bool:
    kind = op_kind(event_name)
    name = op_name(event_name)
    return any(kind.startswith(c) or name.startswith(c)
               for c in COLLECTIVE_OPS)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def measure(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the union of ``a`` that the union of ``b`` does not cover."""
    out: List[Interval] = []
    bu = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in bu:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class DeviceOps:
    """The ops of one device: (start, end, name) in ns, host clock."""
    name: str
    ops: List[Tuple[float, float, str]]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceOps]
    host: List[Tuple[float, float, str]]      # the python thread's events
    marks: List[Tuple[float, int]]            # (time_ns, step index)
    host_lines: Dict[str, int] = dataclasses.field(default_factory=dict)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir!r}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices: List[DeviceOps] = []
    host: List[Tuple[float, float, str]] = []
    host_lines: Dict[str, int] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
            devices.append(DeviceOps(plane.name, sorted(ops)))
        elif plane.name.startswith("/host:"):
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
                     for line in plane.lines}
            # the thread that wrote the markers: ``python`` when the
            # Python tracer names it so, else whichever line holds them
            found = [n for n, evs in lines.items()
                     if any(x.startswith(MARK + " ") for _, _, x in evs)]
            for name in ([HOST_LINE] if HOST_LINE in found else found):
                host.extend(lines[name])
            host_lines.update({f"{plane.name} {n}": len(evs)
                               for n, evs in lines.items()})
    devices.sort(key=lambda d: int(d.name[len(DEVICE_PREFIX):] or 0))
    marks = sorted((s, int(n.split()[-1])) for s, _, n in host
                   if n.startswith(MARK + " "))
    return Trace(devices, sorted(host), marks, host_lines)


def mark_window(trace: Trace, first: int, last: int) -> Interval:
    """Host-clock span from marker ``first`` to marker ``last``."""
    at = {i: t for t, i in trace.marks}
    if first not in at or last not in at:
        raise ValueError(f"markers {first}..{last} not both in the trace "
                         f"(have {sorted(at)}; host lines "
                         f"{trace.host_lines})")
    return at[first], at[last]


@dataclasses.dataclass
class Reduction:
    window_ns: float
    busy_ns: List[float]                    # per device
    exposed_collective_ns: List[float]      # per device
    op_ns: Dict[str, float]                 # self time, summed over devices
    gaps: List[Tuple[str, float]]           # (host activity, ns), longest first

    @property
    def n_devices(self) -> int:
        return len(self.busy_ns)

    @property
    def busy_mean_ns(self) -> float:
        return sum(self.busy_ns) / max(len(self.busy_ns), 1)


def _host_activity(host: Sequence[Tuple[float, float, str]],
                   t: float) -> str:
    """Innermost (shortest) host event covering ``t``."""
    best: Optional[Tuple[float, str]] = None
    for s, e, n in host:
        if s > t:
            break
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "host idle"


def self_times(ops: Sequence[Tuple[float, float, str]]
               ) -> List[Tuple[str, float]]:
    """Each op's time less the time of the ops nested inside it: a
    ``while`` op (a scan over layers) spans the ops of its body on the
    same line, and counting both would count that time twice."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []                 # [start, end, name, nested]

    def close():
        s, e, n, nested = stack.pop()
        out.append((op_name(n), max(e - s - nested, 0.0)))

    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, n, 0.0])
    while stack:
        close()
    return out


def reduce(trace: Trace, window: Interval, *, n_gaps: int = 10
           ) -> Reduction:
    lo, hi = window
    busy, exposed = [], []
    op_ns: Dict[str, float] = {}
    gaps: List[Interval] = []
    for dev in trace.devices:
        ops = [(s, e, n) for s, e, n in dev.ops if e > lo and s < hi]
        spans = clip([(s, e) for s, e, _ in ops], lo, hi)
        busy.append(measure(spans))
        coll = clip([(s, e) for s, e, n in ops if is_collective(n)], lo, hi)
        other = clip([(s, e) for s, e, n in ops if not is_collective(n)],
                     lo, hi)
        exposed.append(measure(subtract(coll, other)))
        for name, t in self_times([(max(s, lo), min(e, hi), n)
                                   for s, e, n in ops]):
            op_ns[name] = op_ns.get(name, 0.0) + t
        gaps.extend(subtract([(lo, hi)], spans))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_activity(trace.host, (gs + ge) / 2), ge - gs)
             for gs, ge in gaps[:n_gaps]]
    return Reduction(hi - lo, busy, exposed, op_ns, named)


def merge(parts: Sequence[Reduction]) -> Reduction:
    """Several windows (e.g. one per mesh) as one: times add up."""
    n = max((p.n_devices for p in parts), default=0)
    op_ns: Dict[str, float] = {}
    for p in parts:
        for k, v in p.op_ns.items():
            op_ns[k] = op_ns.get(k, 0.0) + v
    gaps = sorted((g for p in parts for g in p.gaps), key=lambda g: -g[1])
    return Reduction(
        sum(p.window_ns for p in parts),
        [sum(p.busy_ns[i] for p in parts if i < p.n_devices)
         for i in range(n)],
        [sum(p.exposed_collective_ns[i] for p in parts if i < p.n_devices)
         for i in range(n)],
        op_ns, gaps[:10])


def breakdown(red: Reduction, n: int = 10) -> Dict[str, list]:
    """The result line's ``breakdown``: top device ops (seconds, summed
    over devices) and the longest idle gaps (seconds)."""
    top = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in red.gaps[:n]]}
