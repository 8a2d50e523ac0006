"""Structured tracing: nested spans with ``perf_counter_ns`` timestamps.

A `Tracer` owns a bounded buffer of finished `Span` records and a
per-thread stack of open spans, so ``with trace.span("executor.device_call",
engine="xla"):`` blocks nest naturally.  Every span gets a process-unique
``sid`` and the ``parent`` sid of the span enclosing it on its thread
(or one passed explicitly), so the export rebuilds the
Session -> Executor -> device-call tree from the records alone.

`Tracer.record` books an interval whose two ends were stamped elsewhere,
typically on two threads (a request admitted on a client thread and
served on the drain thread).  Such a record sits at depth 0 and is
marked ``recorded``.

Each span also opens a ``jax.profiler.TraceAnnotation`` of the same
name and labels, so it lands in any running profiler trace on the
profiler's own clock and thread.  jax is imported at the first span,
never before, and left out where it is not installed.

The clock is injectable (``Tracer(clock=...)``): tests drive a
deterministic fake ticker, production uses ``time.perf_counter_ns``.
Every finished span also feeds a latency histogram named
``<span name>_ns`` into the paired `Registry`, keyed only by the labels
in `HISTOGRAM_LABELS` (each takes a few values), so a per-request label
such as ``seq`` stays on the record without making a series per request.

The buffer is bounded (``max_spans``); once full, new spans still time
and feed histograms but their records are dropped and counted in
``spans_dropped`` — bounded memory, no silent truncation.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import numbers
import threading
import time

#: The labels a span histogram is keyed by; every other label stays on
#: the span record only.
HISTOGRAM_LABELS = frozenset({"kind", "engine", "stage", "mode", "phase",
                              "space", "evaluator", "shard", "op"})


@dataclasses.dataclass
class Span:
    """One finished span: a named, labeled [t0, t0+dur) interval."""

    name: str
    t0_ns: int
    dur_ns: int
    depth: int              # nesting depth at record time (0 = root)
    tid: int                # OS thread ident (trace-viewer lane)
    labels: dict
    sid: int = 0            # process-unique span id
    parent: int = None      # sid of the enclosing span (None = root)
    recorded: bool = False  # booked by `Tracer.record` (ends on two threads)

    @property
    def t1_ns(self) -> int:
        return self.t0_ns + self.dur_ns


@functools.cache
def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use (None
    where jax is not installed)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


def _profiler_labels(labels: dict) -> dict:
    """Labels as the profiler stores them: integers stay, the rest are
    strings."""
    return {k: int(v) if isinstance(v, numbers.Integral) else str(v)
            for k, v in labels.items()}


class _SpanCtx:
    """The context manager `Tracer.span` returns when tracing is live."""

    __slots__ = ("_tracer", "name", "labels", "t0", "depth", "sid",
                 "parent", "_ann")

    def __init__(self, tracer, name, labels, parent):
        self._tracer = tracer
        self.name = name
        self.labels = labels
        self.parent = parent
        self._ann = None

    def __enter__(self):
        tracer = self._tracer
        stack = tracer._stack()
        self.depth = len(stack)
        self.sid = next(tracer._sids)
        if self.parent is None and stack:
            self.parent = stack[-1].sid
        stack.append(self)
        cls = _annotation()
        if cls:
            self._ann = cls(self.name, **_profiler_labels(self.labels))
            self._ann.__enter__()
        self.t0 = tracer.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = self._tracer.clock() - self.t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._finish(Span(
            name=self.name, t0_ns=self.t0, dur_ns=dur, depth=self.depth,
            tid=threading.get_ident(), labels=self.labels, sid=self.sid,
            parent=self.parent))

    def label(self, **labels) -> "_SpanCtx":
        """Attach labels discovered after the span opened (chainable)."""
        self.labels.update(labels)
        if self._ann is not None:
            self._ann.set_metadata(**_profiler_labels(labels))
        return self


class _NullSpan:
    """What `span` hands out while tracing is disabled: a shared, inert
    context manager (no allocation on the disabled hot path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        pass

    def label(self, **labels) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class Tracer:
    """Span buffer + per-thread open-span stacks (module docstring)."""

    def __init__(self, clock=time.perf_counter_ns, registry=None,
                 max_spans: int = 200_000):
        self.clock = clock
        self.registry = registry
        self.max_spans = max_spans
        self.spans = []
        self.spans_dropped = 0
        self._sids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, parent: int = None, **labels) -> _SpanCtx:
        return _SpanCtx(self, name, labels, parent)

    def record(self, name: str, t0_ns: int, t1_ns: int, parent: int = None,
               **labels) -> int:
        """Book the finished interval [t0_ns, t1_ns) at depth 0; returns
        its sid."""
        sid = next(self._sids)
        self._finish(Span(name=name, t0_ns=t0_ns, dur_ns=t1_ns - t0_ns,
                          depth=0, tid=threading.get_ident(), labels=labels,
                          sid=sid, parent=parent, recorded=True))
        return sid

    def _finish(self, span: Span) -> None:
        with self._lock:
            if len(self.spans) < self.max_spans:
                self.spans.append(span)
            else:
                self.spans_dropped += 1
        if self.registry is not None:
            self.registry.histogram(
                span.name + "_ns",
                **{k: v for k, v in span.labels.items()
                   if k in HISTOGRAM_LABELS}).observe(span.dur_ns)

    def snapshot(self) -> list:
        with self._lock:
            return list(self.spans)

    def reset(self) -> None:
        with self._lock:
            self.spans.clear()
            self.spans_dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self.spans)
