"""The program's span log (``repro.core.spans``) as the per-layer metrics
read it.

The log is process-wide and survives the trace directory, so a metric
finds the window's records by their groups: every span of one monitor
segment carries its ``MonitorSession``'s group, every span of one served
batch that batch's group.  The window's groups are the last ones that hold
a record of the segment's first span (``capture.lower`` for a report,
``serve.prefill`` for a batch).  The warm-up cycle made exactly one group
of each kind before them; where that one is gone too, the ring dropped
records and nothing is read (it keeps records in the order they closed, so
while the warm-up's survives, so does everything after it).  On a program
without the log every reader reads nothing.
"""
from __future__ import annotations

import trace_reduce

JAXPR = "/jax/core/compile/jaxpr_trace_duration"
MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
JAX_EVENTS = (JAXPR, MLIR, "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
SERVE_SPANS = ("serve.prefill", "serve.decode", "serve.sample")


class Window:
    """The records of the window's groups."""

    def __init__(self, records: list[dict]):
        self.records = records
        self.by_id = {r["id"]: r for r in records}

    def _under(self, r: dict, prefix: str) -> bool:
        """Whether an ancestor of ``r`` is named ``prefix...``."""
        p = self.by_id.get(r["parent"])
        while p is not None:
            if p["name"].startswith(prefix):
                return True
            p = self.by_id.get(p["parent"])
        return False

    def named(self, names, outside: str | None = None) -> list[dict]:
        return [r for r in self.records if r["name"] in names
                and not (outside and self._under(r, outside))]

    def seconds(self, names, outside: str | None = None) -> float:
        """Summed length of the records named ``names``, leaving out those
        under a span named ``outside...``."""
        return sum(r["end_ns"] - r["start_ns"]
                   for r in self.named(names, outside)) / 1e9

    def self_seconds(self, names, outside: str | None = None) -> float:
        """As :meth:`seconds`, less the length of their direct children."""
        ids = {r["id"] for r in self.named(names, outside)}
        kids = sum(r["end_ns"] - r["start_ns"] for r in self.records
                   if r["parent"] in ids)
        return self.seconds(names, outside) - kids / 1e9

    def jax_seconds(self, events, under) -> float:
        """The union of the JAX compile-path events ``events`` whose parent
        span is named one of ``under`` (nested traces and a compile's cache
        read counted once)."""
        merged = trace_reduce._union(
            (r["start_ns"], r["end_ns"]) for r in self.records
            if r["name"] in events
            and self.by_id.get(r["parent"], {}).get("name") in under)
        return sum(e - s for s, e in merged) / 1e9

    def counter(self, name: str) -> int:
        return sum((r["counts"] or {}).get(name, 0) for r in self.records)


def window(mark: str, n: int) -> Window | None:
    """The records of the last ``n`` groups holding a record named
    ``mark``, or None (see the module docstring)."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    if n <= 0:
        return None
    records = spans.snapshot()["records"]
    groups = sorted({r["group"] for r in records
                     if r["name"] == mark and r["group"] is not None})
    if len(groups) < n + 1:
        return None
    keep = set(groups[-n:])
    return Window([r for r in records if r["group"] in keep])


def reports(run) -> Window | None:
    """The window's monitor segments."""
    return window("capture.lower", run.reports)


def batches(run) -> Window | None:
    """The window's served batches."""
    batch = run.cell.traffic.get("batch")
    if not batch:
        return None
    return window("serve.prefill", run.counts.get("requests", 0) // batch)
