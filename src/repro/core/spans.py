"""Spans and counters: where the monitor and the served job spend host time.

``span(name, group)`` times a block of code::

    with spans.span("capture.lower", group=g) as s:
        lowered = jitted.lower(*args)
    s.seconds                          # the block's length

It does two things.  It enters ``jax.profiler.TraceAnnotation(name)``, so
when the profiler runs the span lands in the trace's host plane, on the
profiler's clock, around the device work it dispatched.  And it appends
one record to a process-wide log: ``name``, ``start_ns`` and ``end_ns``
(``time.time_ns()``, the wall clock the profiler's host events use),
``parent`` (the id of the innermost span open on the thread, or None),
``group`` and ``counts``.  A span given no group takes its parent's, or,
at the top, the one a ``with group(g):`` block set on the thread.  Groups
tie the records of one unit of work together: a
:class:`~repro.core.session.MonitorSession` enters its ``span_group``
around its captures and ``report()``, and the report carries it to its
exports (``CommReport.span_group``); the spans of one
:func:`~repro.serve.serve.generate` call share that batch's group.

``count(name, n)`` adds ``n`` to a counter of the innermost open span; the
span's record carries its counters.

A duration listener on ``jax.monitoring`` turns JAX's own compile-path
events (:data:`JAX_EVENTS`: tracing to a jaxpr, lowering to MLIR, the
backend compile, a persistent-cache read) into child records of the
innermost open span on the thread: named by the event, ending when JAX
reports it and starting ``duration`` earlier.  Events that arrive with no
span open are not recorded.  A backend compile that reads the persistent
cache reports both events, the read inside the compile: a reader that sums
JAX time takes the union of the intervals.

The log is a ring of :data:`CAPACITY` records, always on; when it is full
the oldest record is dropped and counted.  Records are kept in the order
they closed, so where any record of a unit of work survives, everything
that closed after it survives too.  Record ids and group ids come from one
counter: ids grow with the time a span opened, and groups with the time
they were made.  The operator reads it in-process::

    from repro.core import spans
    spans.snapshot()                   # {"records": [...], "dropped": n, ...}
    spans.dump("spans.json")           # the same, as JSON

Spans and counters in the program, and what reads them (the benchmark's
per-layer metrics, ``bench/metrics/``):

  capture.lower, capture.compile      ``Capture.trace_seconds`` /
                                      ``compile_seconds``; the JAX events
                                      under capture.lower give
                                      capture.jaxpr_s and capture.mlir_s
  capture.hlo_text                    capture.hlo_text_s
  capture.analyze_hlo                 capture.analyze_s
  capture.analyses                    capture.analyses_s
  view.schedule                       report.schedule_s
  view.matrix, view.per_primitive     report.place_s
  export.json, export.html            report.export_json_s, _html_s
  serve.prefill, serve.decode, serve.sample
                                      serve.retrace_s, serve.dispatch_ms

The one counter, ``serve.decode_steps`` on each ``serve.decode`` span, is
serve.dispatch_ms's divisor.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
from typing import Optional

import jax

CAPACITY = 1 << 16

JAX_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
})

FIELDS = ("id", "name", "start_ns", "end_ns", "parent", "group", "counts")

_ids = itertools.count(1)
_TraceAnnotation = jax.profiler.TraceAnnotation


class _Local(threading.local):
    def __init__(self):
        self.stack: list = []
        self.group: Optional[int] = None


_local = _Local()


class _Log:
    """A bounded ring of record tuples (:data:`FIELDS`) that counts the
    records it dropped."""

    def __init__(self, capacity: int):
        self.ring: collections.deque = collections.deque(maxlen=capacity)
        self.dropped = 0
        self.lock = threading.Lock()

    def append(self, rec: tuple) -> None:
        with self.lock:
            if len(self.ring) == self.ring.maxlen:
                self.dropped += 1
            self.ring.append(rec)

    def snapshot(self) -> dict:
        with self.lock:
            recs, dropped = list(self.ring), self.dropped
        return {"capacity": self.ring.maxlen, "dropped": dropped,
                "records": [dict(zip(FIELDS, r)) for r in recs]}


_LOG = _Log(CAPACITY)


def new_group() -> int:
    """A fresh group id, larger than every id made before it."""
    return next(_ids)


@contextlib.contextmanager
def group(g: Optional[int]):
    """Give group ``g`` to the spans opened in the block on this thread
    that have neither a group of their own nor an open parent."""
    outer, _local.group = _local.group, g
    try:
        yield
    finally:
        _local.group = outer


class span:  # noqa: N801  (a context manager, named like contextlib's)
    """One timed block; see the module docstring.  ``seconds`` is its
    length once it has closed."""

    __slots__ = ("name", "group", "id", "parent", "start_ns", "end_ns",
                 "counts", "_annotation")

    def __init__(self, name: str, group: Optional[int] = None):
        self.name = name
        self.group = group
        self.counts: Optional[dict] = None

    def __enter__(self) -> "span":
        stack = _local.stack
        parent = stack[-1] if stack else None
        self.parent = parent.id if parent is not None else None
        if self.group is None:
            self.group = parent.group if parent is not None else _local.group
        self.id = next(_ids)
        self._annotation = _TraceAnnotation(self.name)
        self._annotation.__enter__()
        stack.append(self)
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.time_ns()
        _local.stack.pop()
        self._annotation.__exit__(*exc)
        _LOG.append((self.id, self.name, self.start_ns, self.end_ns,
                     self.parent, self.group, self.counts))
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span (nothing
    where no span is open)."""
    stack = _local.stack
    if not stack:
        return
    s = stack[-1]
    if s.counts is None:
        s.counts = {}
    s.counts[name] = s.counts.get(name, 0) + n


def _on_duration(event: str, duration: float, **_) -> None:
    if event not in JAX_EVENTS:
        return
    stack = _local.stack
    if not stack:
        return
    parent = stack[-1]
    end = time.time_ns()
    _LOG.append((next(_ids), event, end - round(duration * 1e9), end,
                 parent.id, parent.group, None))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> dict:
    """The log as it stands: ``records`` (in the order they closed, one
    dict of :data:`FIELDS` each), ``capacity`` and ``dropped``."""
    return _LOG.snapshot()


def dump(path: str) -> str:
    """Write :func:`snapshot` to ``path`` as JSON; returns ``path``."""
    with open(path, "w") as f:
        json.dump(snapshot(), f)
    return path
