"""Nestable span tracing for host phases and device work.

A :class:`Span` is a context manager that times one phase (plan, lower,
compile, step, ...).  Spans nest: each thread keeps a stack, so a span
opened inside another records the outer span's id as its ``parent`` — the
JSONL trace events reconstruct the tree.  For device work, async dispatch
makes naive host timing meaningless; register the step's outputs with
:meth:`Span.block` and the span closes over ``jax.block_until_ready`` so
the recorded duration covers real execution, not just dispatch.

Every span holds a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` open while it runs, so it lands on the profiler's own
clock beside the device's ops whenever a ``jax.profiler`` trace is being
taken (and costs about a microsecond when none is).  The annotation
carries the name only: attributes would be encoded into the event name
and split one phase into many labels.

Every closed span (a) appends a ``{"kind": "span", ...}`` event to the
tracer's sink and (b) observes its duration into the ``span.<name>.s``
histogram of the tracer's metric registry — so the same measurement feeds
both the raw trace and the p50/p99 summaries the drift report consumes.
:class:`AnnotationSpan` is the annotation alone, what a disabled
:class:`~repro.obs.Obs` hands out: no clock read, no event, no histogram,
no sync.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: prefix of every span's name in a profiler trace
PREFIX = "repro."


class Span:
    """One timed phase; use via ``with tracer.span("step") as sp:``."""

    __slots__ = ("name", "attrs", "id", "parent", "t_wall", "seconds",
                 "_tracer", "_t0", "_sync", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.id: Optional[int] = None
        self.parent: Optional[int] = None
        self.t_wall: float = 0.0
        self.seconds: float = 0.0
        self._tracer = tracer
        self._t0: float = 0.0
        self._sync: List[Any] = []
        self._ann: Optional[TraceAnnotation] = None

    def block(self, value):
        """Register device output(s) to ``block_until_ready`` at close.

        Returns ``value`` unchanged so the call slots into assignments:
        ``out = sp.block(fn(x))``.
        """
        self._sync.append(value)
        return value

    def __enter__(self) -> "Span":
        # the annotation starts when it is made, so it is made here
        self._ann = TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.id = self._tracer._next_id()
        stack = self._tracer._stack()
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self.t_wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            if self._sync:
                import jax
                jax.block_until_ready(self._sync)
                self._sync.clear()
            self.seconds = time.perf_counter() - self._t0
            stack = self._tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
            self._tracer._emit(self, error=exc_type.__name__ if exc_type
                               else None)
        finally:
            self._ann.__exit__(exc_type, exc, tb)


class AnnotationSpan:
    """The profiler annotation of a span and nothing else: what a
    disabled obs returns, so program spans reach any ``jax.profiler``
    trace with telemetry off.  :meth:`block` does not sync."""

    __slots__ = ("name", "_ann")
    id = None
    parent = None
    seconds = 0.0

    def __init__(self, name: str):
        self.name = name

    def block(self, value):
        return value

    def __enter__(self) -> "AnnotationSpan":
        self._ann = TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._ann.__exit__(exc_type, exc, tb)


class Tracer:
    """Span factory wired to a sink (JSONL events) and a metric registry
    (``span.<name>.s`` histograms).  Either may be None."""

    def __init__(self, sink=None, metrics=None):
        self.sink = sink
        self.metrics = metrics
        self._ids = itertools.count(1)
        self._id_lock = threading.Lock()
        self._tls = threading.local()

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    # ------------------------------------------------------------------
    def _next_id(self) -> int:
        with self._id_lock:
            return next(self._ids)

    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _emit(self, span: Span, error: Optional[str] = None) -> None:
        if self.metrics is not None:
            self.metrics.histogram(f"span.{span.name}.s").observe(
                span.seconds)
        if self.sink is not None:
            # attrs first: the reserved keys must win a collision (a span
            # attr named "kind" would otherwise corrupt the event type)
            event = {**span.attrs,
                     "kind": "span", "name": span.name, "id": span.id,
                     "parent": span.parent, "t_wall": span.t_wall,
                     "dur_s": span.seconds}
            if error:
                event["error"] = error
            self.sink.write(event)
