"""Host spans on the profiler's clock, and the compiles between them.

``span(name)`` always opens a ``jax.profiler.TraceAnnotation``, so the
span lands on the profiler's host line beside the device ops when a
profiler session runs (about a microsecond when none does).  Inside a
``recording()`` block it is also kept by the active :class:`Recorder`
as a :class:`Span`: name, ``perf_counter_ns`` start and end, and the
enclosing span.  Outside ``recording()`` nothing is stored: the
caller decides whether tracing is on.

While a recorder is active, each backend compile of a jitted program
(JAX's ``backend_compile_duration`` event) is kept too, as a timed
``compile`` span, so a compile can be placed between steps; a read from
the persistent compilation cache runs inside it, so it counts too.

Recorders nest: a span goes to every active recorder, so a caller can
count the compiles of one call inside a longer recording.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import time
from typing import Deque, Dict, Iterator, List, Optional

import jax

COMPILE = "compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the newest spans a recorder keeps; its totals count every span
MAX_SPANS = 100_000


class Span:
    """One timed stretch of host work; ``parent`` is the span that was
    open around it on the same thread, if any."""
    __slots__ = ("name", "start_ns", "end_ns", "parent")

    def __init__(self, name: str, start_ns: int = 0, end_ns: int = 0,
                 parent: Optional["Span"] = None):
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.parent = parent

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.start_ns}, {self.end_ns}, "
                f"parent={self.parent.name if self.parent else None!r})")


@dataclasses.dataclass
class Total:
    """Per-name totals: how many spans, and their summed duration in ns."""
    count: int = 0
    sum: float = 0.0

    def add(self, value: float) -> None:
        self.count += 1
        self.sum += value


class Recorder:
    """Bounded store of one ``recording()`` block: per-name totals of
    every span, and the newest ``MAX_SPANS`` spans."""

    def __init__(self):
        self.spans: Deque[Span] = collections.deque(maxlen=MAX_SPANS)
        self.totals: Dict[str, Total] = {}

    def add_span(self, s: Span) -> None:
        self.spans.append(s)
        t = self.totals.get(s.name)
        if t is None:
            t = self.totals[s.name] = Total()
        t.add(s.end_ns - s.start_ns)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def children(self, parent: Span) -> List[Span]:
        return [s for s in self.spans if s.parent is parent]


_ACTIVE: List[Recorder] = []
_CURRENT: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "repro_tracing_span", default=None)


@contextlib.contextmanager
def _timed(name: str, annotation) -> Iterator[Span]:
    s = Span(name, parent=_CURRENT.get())
    token = _CURRENT.set(s)
    try:
        with annotation:
            s.start_ns = time.perf_counter_ns()
            try:
                yield s
            finally:
                s.end_ns = time.perf_counter_ns()
    finally:
        _CURRENT.reset(token)
        for r in _ACTIVE:
            r.add_span(s)


def span(name: str) -> contextlib.AbstractContextManager:
    """Time the ``with`` body as span ``name``; yields the :class:`Span`,
    whose times are set on exit whether or not a recorder is active."""
    return _timed(name, jax.profiler.TraceAnnotation(name))


def step_span(name: str, step: int) -> contextlib.AbstractContextManager:
    """``span`` as a ``jax.profiler.StepTraceAnnotation``: the profiler
    takes it as the boundary of step ``step``."""
    return _timed(name, jax.profiler.StepTraceAnnotation(name,
                                                         step_num=step))


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Activate a new :class:`Recorder` for the ``with`` body, with a
    listener on JAX's backend-compile events."""
    rec = Recorder()

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            end = time.perf_counter_ns()
            rec.add_span(Span(COMPILE, end - int(secs * 1e9), end,
                              _CURRENT.get()))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _ACTIVE.append(rec)
    try:
        yield rec
    finally:
        _ACTIVE.remove(rec)
        jax.monitoring.unregister_event_duration_listener(on_duration)
