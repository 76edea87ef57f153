"""Session-based monitoring: multi-phase capture over a whole run.

The paper's tool monitors a *running application*: it accumulates transfers
across the execution and post-processes them afterwards.  Real workloads
have *phases* -- fwd/bwd/optimizer in a train step, prefill/decode on the
serve path, per-iteration segments of an NCCL-style phase analysis -- and a
one-shot wrapper around a single jitted function cannot tell them apart.

:class:`MonitorSession` is the accumulating front door::

    with MonitorSession(mesh=mesh, name="train") as sess:
        with sess.phase("fwd"):
            sess.capture(fwd_step, params, batch)
        with sess.phase("bwd"):
            sess.capture(bwd_step, params, batch)
        with sess.phase("optim"):
            sess.capture(opt_step, params, grads, opt_state)

    sess.view()                    # whole-session CommView (lazy, memoized)
    sess.view(phase="bwd")         # one phase's matrices / summaries
    sess.view("tree")              # re-bound algorithm, no recompilation
    report = sess.report()         # serializable CommReport snapshot (v5)

Each :meth:`capture` traces one function under the interceptor, compiles
it, parses the collective schedule, and tags every op / traced event /
host transfer with the active phase.  Derived artifacts are never built
eagerly -- :meth:`view` hands out :class:`~repro.core.views.CommView`
bindings that memoize on first read -- and :meth:`report` snapshots the
session into a :class:`~repro.core.monitor.CommReport` whose schema-v5
serialization round-trips the phase structure.

``monitor_fn`` (:mod:`repro.core.monitor`) survives as a thin
compatibility wrapper: one capture in one phase, artifact-for-artifact
identical to the session path (golden-tested).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Iterable, Optional

import jax
import numpy as np

from . import cost_models, decompose, hlo_cost, spans
from .events import (CollectiveOp, HostTransfer, PhaseRecord, TraceEvent)
from .interceptor import CollectiveInterceptor, traced_summary
from .topology import MeshTopology
from .views import CommView, build_view

DEFAULT_PHASE = "main"


def _memory_stats(compiled) -> dict:
    """The compiler's per-device memory analysis.  A backend that cannot
    give one fails the capture: a report without it has no memory stats
    and no roofline, which must not pass for a finished capture."""
    m = compiled.memory_analysis()
    return {
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "generated_code_bytes": m.generated_code_size_in_bytes,
        "total_bytes": (m.argument_size_in_bytes + m.output_size_in_bytes
                        + m.temp_size_in_bytes - m.alias_size_in_bytes),
    }


def _cost_analysis(compiled) -> dict:
    """The compiler's cost analysis (FLOPs, bytes accessed); raises where
    the backend gives none, for the same reason as :func:`_memory_stats`."""
    cost = compiled.cost_analysis()
    if not cost:
        raise RuntimeError("the compiler returned no cost analysis")
    return dict(cost)


@dataclasses.dataclass
class Capture:
    """One monitored function inside a session (trace + compile + parse).

    Carries parsed artifacts only; the live XLA executables of the most
    recent capture live on the session (``last_lowered``/``last_compiled``)
    so a long session does not pin one compiled executable per capture.
    """

    name: str
    phase: str
    ops: list[CollectiveOp]
    traced: list[TraceEvent]
    trace_seconds: float
    compile_seconds: float
    cost: dict
    memory_stats: dict
    hlo_text: str = ""


class MonitorSession:
    """Accumulating, phase-aware monitoring context (see module docstring).

    ``mesh`` fixes the device topology for every capture; ``algorithm`` is
    the default binding of the views and the snapshot report (validated
    here, so a typo fails before anything compiles).  The session object is
    reusable as a plain accumulator -- the ``with`` block is bookkeeping
    sugar, not a resource: captures outside it work identically.
    """

    def __init__(self, mesh=None, name: str = "session",
                 algorithm: str = "ring",
                 sparse: Optional[bool] = None):
        cost_models.validate_algorithm(algorithm)
        # a fresh session warns afresh: hierarchical-fallback warnings are
        # deduplicated per (kind, group size) per session, not per process
        decompose.reset_fallback_warnings()
        self.mesh = mesh
        self.name = name
        self.algorithm = algorithm
        # matrix representation for every view/snapshot of this session:
        # True = COO SparseCommMatrix, False = dense, None = auto by
        # device count (views.SPARSE_DEVICE_THRESHOLD)
        self.sparse = sparse
        # the group of every span of this session's captures, views and
        # exports (repro.core.spans)
        self.span_group = spans.new_group()
        self.topo = MeshTopology.from_mesh(mesh) if mesh is not None else None
        self.num_devices = (int(np.prod(mesh.devices.shape))
                            if mesh is not None else jax.device_count())
        self.captures: list[Capture] = []
        self.host_transfers: list[HostTransfer] = []
        self.last_lowered: Any = None      # live artifacts of the most
        self.last_compiled: Any = None     # recent capture only
        self._phases: dict[str, PhaseRecord] = {}   # insertion == phase order
        self._phase_stack: list[str] = []
        self._views: dict = {}

    # -- context plumbing --------------------------------------------------
    def __enter__(self) -> "MonitorSession":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    @contextlib.contextmanager
    def phase(self, name: str):
        """Scope subsequent captures under phase ``name`` (re-enterable:
        capturing into an existing phase accumulates into its record)."""
        if not name:
            raise ValueError("phase name must be non-empty")
        self._phase_record(name)      # fix ordering at first entry
        self._phase_stack.append(name)
        try:
            yield self
        finally:
            self._phase_stack.pop()

    @property
    def current_phase(self) -> str:
        return self._phase_stack[-1] if self._phase_stack else DEFAULT_PHASE

    def _phase_record(self, name: str) -> PhaseRecord:
        if name not in self._phases:
            self._phases[name] = PhaseRecord(name=name)
        return self._phases[name]

    # -- capture -----------------------------------------------------------
    def capture(
        self,
        fn,
        *args,
        name: Optional[str] = None,
        phase: Optional[str] = None,
        in_shardings=None,
        out_shardings=None,
        donate_argnums=(),
        static_argnums=(),
        host_transfers: Optional[Iterable[HostTransfer]] = None,
        op_transform=None,
        **kwargs,
    ) -> Capture:
        """Monitor one function: trace (intercepted) + compile + parse.

        ``args``/``kwargs`` may be concrete arrays or
        ``jax.ShapeDtypeStruct`` stand-ins (nothing executes; no device
        memory is allocated).  A ``jax.jit``-wrapped ``fn`` given no jit
        options here is captured as it is, so the report describes the
        program the caller runs.  The parsed ops and traced events are tagged
        with ``phase`` (default: the innermost active :meth:`phase`, else
        ``"main"``) and accumulated into the session.

        ``op_transform`` (``CollectiveOp -> CollectiveOp``, optional) is
        applied to every parsed op before it is accumulated -- the hook
        captured runtime knowledge the HLO cannot carry, e.g. injecting a
        measured per-rank byte vector (``bytes_per_rank_vec``) onto an
        all-to-all whose expert routing is skewed.  Returning the op
        unchanged is fine; returning ``None`` keeps the original.
        """
        phase_name = phase or self.current_phase
        rec = self._phase_record(phase_name)

        jit_kw: dict[str, Any] = {}
        if in_shardings is not None:
            jit_kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            jit_kw["out_shardings"] = out_shardings
        if donate_argnums:
            jit_kw["donate_argnums"] = donate_argnums
        if static_argnums:
            jit_kw["static_argnums"] = static_argnums
        # an already-jitted fn with no options of its own is lowered as it
        # is: the capture is of the very object the caller runs
        jitted = (fn if not jit_kw and hasattr(fn, "lower")
                  else jax.jit(fn, **jit_kw))
        if jitted is fn:
            # a trace cached by an earlier call would hide its collectives
            # from the interceptor; the next call of fn compiles again
            fn.clear_cache()

        with spans.group(self.span_group):
            with spans.span("capture.lower") as lower:
                with CollectiveInterceptor(mesh=self.mesh) as icpt:
                    lowered = jitted.lower(*args, **kwargs)
            with spans.span("capture.compile") as comp:
                compiled = lowered.compile()
            with spans.span("capture.hlo_text"):
                hlo_text = compiled.as_text()
            with spans.span("capture.analyze_hlo"):
                # loop-aware extraction: ops inside while bodies carry trip
                # weights
                ops = hlo_cost.analyze_hlo(hlo_text).collectives
            with spans.span("capture.analyses"):
                cost = _cost_analysis(compiled)
                memory_stats = _memory_stats(compiled)
        if op_transform is not None:
            ops = [op_transform(op) or op for op in ops]
        for op in ops:
            op.phase = phase_name
        events = list(icpt.events)
        for ev in events:
            ev.phase = phase_name

        cap = Capture(
            name=name or getattr(fn, "__name__", "fn"),
            phase=phase_name,
            ops=ops,
            traced=events,
            trace_seconds=lower.seconds,
            compile_seconds=comp.seconds,
            cost=cost,
            memory_stats=memory_stats,
            hlo_text=hlo_text,
        )
        self.captures.append(cap)
        self.last_lowered = lowered
        self.last_compiled = compiled
        rec.num_captures += 1
        rec.trace_seconds += cap.trace_seconds
        rec.compile_seconds += cap.compile_seconds
        if host_transfers:
            self.add_host_transfers(host_transfers, phase=phase_name)
        self._views.clear()           # accumulated state changed
        return cap

    def add_host_transfers(self, transfers: Iterable[HostTransfer],
                           phase: Optional[str] = None):
        """Record host<->device transfers (paper row/col 0), phase-tagged.

        Untagged transfers are *copied* with the active phase (never
        mutating the caller's objects, so a list reused across phases
        records once per phase as expected); a transfer arriving with its
        own phase tag registers that phase so per-phase views see it.
        """
        phase_name = phase or self.current_phase
        self._phase_record(phase_name)
        for t in transfers:
            if not t.phase:
                t = dataclasses.replace(t, phase=phase_name)
            else:
                self._phase_record(t.phase)
            self.host_transfers.append(t)
        self._views.clear()

    # -- accumulated state -------------------------------------------------
    @property
    def compiled_ops(self) -> list[CollectiveOp]:
        return [op for cap in self.captures for op in cap.ops]

    @property
    def traced(self) -> list[TraceEvent]:
        return [ev for cap in self.captures for ev in cap.traced]

    @property
    def trace_seconds(self) -> float:
        return sum(c.trace_seconds for c in self.captures)

    @property
    def compile_seconds(self) -> float:
        return sum(c.compile_seconds for c in self.captures)

    def phase_names(self) -> list[str]:
        return list(self._phases)

    # -- views and snapshots -----------------------------------------------
    def view(self, algorithm: Optional[str] = None,
             phase: Optional[str] = None) -> CommView:
        """Lazy :class:`CommView` of the session (or one ``phase``) bound
        to ``algorithm`` (default: the session's).  Memoized per
        ``(algorithm, phase)``; invalidated by the next capture."""
        alg = algorithm or self.algorithm
        cost_models.validate_algorithm(alg)
        key = (alg, phase)
        if key not in self._views:
            self._views[key] = build_view(
                self.compiled_ops, self.num_devices, alg, self.topo,
                self.host_transfers, phase=phase,
                known_phases=self.phase_names(), label=self.name,
                sparse=self.sparse,
                hlo_texts=[c.hlo_text for c in self.captures])
        return self._views[key]

    def _merged_cost(self) -> dict:
        if len(self.captures) == 1:
            return dict(self.captures[0].cost)
        out: dict[str, float] = {}
        for cap in self.captures:
            for k, v in cap.cost.items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0.0) + float(v)
        return out

    def _merged_memory_stats(self) -> dict:
        if len(self.captures) == 1:
            return self.captures[0].memory_stats
        out: dict[str, float] = {}
        for cap in self.captures:
            for k, v in cap.memory_stats.items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + v
        return out

    def report(self, name: Optional[str] = None):
        """Snapshot the session into a serializable
        :class:`~repro.core.monitor.CommReport` (schema v5: per-phase op
        lists and phase records ride along; ``save``/``load`` round-trips
        them).  The compiled HLO of every capture is attached as
        ``_hlo_texts`` (one module per capture -- analyzed per module, a
        concatenation would clobber same-named computations), and the most
        recent capture's live artifacts as ``_lowered``/``_compiled``, so
        ``roofline_of`` works in-process; persist the HLO with
        ``save(..., include_hlo=True)`` to keep rooflines working on
        loaded reports.
        """
        from .monitor import CommReport   # deferred: monitor imports us

        with spans.group(self.span_group):     # the views' spans
            v = self.view()
            rep = CommReport(
                name=name or self.name,
                num_devices=self.num_devices,
                traced=list(self.traced),
                compiled_ops=list(self.compiled_ops),
                traced_summary=traced_summary(self.traced),
                compiled_summary=v.summary,
                matrix=v.matrix,
                per_primitive=v.per_primitive,
                cost=self._merged_cost(),
                memory_stats=self._merged_memory_stats(),
                trace_seconds=self.trace_seconds,
                compile_seconds=self.compile_seconds,
                topo=self.topo,
                host_transfers=list(self.host_transfers),
                algorithm=self.algorithm,
                phases=[dataclasses.replace(p)
                        for p in self._phases.values()],
                span_group=self.span_group,
            )
        if self.captures:
            rep._lowered = self.last_lowered
            rep._compiled = self.last_compiled
            rep._hlo_texts = [c.hlo_text for c in self.captures]
            if len(self.captures) == 1:
                rep._hlo_text = self.captures[0].hlo_text
        return rep
