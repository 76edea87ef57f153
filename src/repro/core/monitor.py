"""High-level monitoring API — ComScribe's workflow, end to end.

The paper's workflow (Fig. 1): preload shim -> record transfers during
execution -> post-process into matrices + statistics.  Ours:

1. **intercept**: trace each captured function under a scoped primitive hook
   (:mod:`repro.core.interceptor`) -> logical, application-issued collectives;
2. **extract**: compile and parse the SPMD module
   (:mod:`repro.core.hlo_parser`) -> physical, compiler-scheduled collectives;
3. **post-process**: per-primitive statistics (Tables 2/3), ``(d+1)^2``
   communication matrices (Figs. 2/3), logical-vs-physical diff, and the
   roofline terms used by the perf loop.

The accumulating front door is :class:`~repro.core.session.MonitorSession`
(any number of captures under named phases); derived artifacts live on lazy
:class:`~repro.core.views.CommView` bindings (``session.view()`` /
``report.view()``), one per ``(algorithm, phase)``.  ``monitor_fn`` below is
the one-call compatibility wrapper -- a single capture in a single phase --
still used by examples, benchmarks, the dry-run launcher and the sweep CLI.
Reports round-trip losslessly through :meth:`CommReport.save` /
:meth:`CommReport.load` (schema v5, :mod:`repro.core.export.serialize`;
v1-v4 files still load), which is also how the on-disk report cache
(:mod:`repro.core.report_cache`) lets repeated sweeps skip recompilation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from . import cost_models, hlo_parser, reporter, roofline
from .events import CollectiveOp, HostTransfer, PhaseRecord, TraceEvent
from .sparse import is_sparse
from .topology import MeshTopology, V5E
from .views import CommView, build_view


@dataclasses.dataclass
class CommReport:
    """Everything ComScribe produces for one session, plus the TPU extras.

    A report is the *serializable snapshot* of a monitoring session: it
    serializes losslessly to JSON via :meth:`save` and comes back via
    :meth:`load`, so sweeps can cache it on disk
    (:mod:`repro.core.report_cache`) keyed by ``(config, mesh, algorithm,
    jax version)`` and re-render any export format without recompiling.
    ``phases`` records the session's named capture phases (empty for
    legacy single-shot reports); every op / traced event / host transfer
    carries its phase tag, so per-phase views rebuild from loaded files.

    ``algorithm`` records which collective algorithm the eager byte
    accounting (``matrix``, ``per_primitive``, ``compiled_summary``) was
    derived with.  Every *derived* artifact beyond those snapshot fields is
    served by :meth:`view`: a lazy, memoized
    :class:`~repro.core.views.CommView` per ``(algorithm, phase)`` binding
    -- re-binding ring -> tree -> hierarchical recomputes nothing until an
    artifact is read, and never recompiles.

    Export beyond the terminal renderings below lives in
    :mod:`repro.core.export` (JSON / CSV / HTML heatmap dashboard / Perfetto
    timeline), or from the shell::

        python -m repro report artifacts/quickstart_report.json \\
            --formats html,perfetto --out artifacts/
    """

    name: str
    num_devices: int
    traced: list[TraceEvent]
    compiled_ops: list[CollectiveOp]
    traced_summary: dict
    compiled_summary: dict
    # (d+1)x(d+1) bytes, row/col 0 host: a dense ndarray, or the COO
    # SparseCommMatrix form at fleet scale (sparse sessions / loaded v6)
    matrix: np.ndarray
    per_primitive: dict[str, np.ndarray]
    cost: dict
    memory_stats: Optional[dict]
    trace_seconds: float
    compile_seconds: float
    topo: Optional[MeshTopology] = None
    host_transfers: list[HostTransfer] = dataclasses.field(default_factory=list)
    algorithm: str = "ring"                 # algorithm the matrices assume
    meta: dict = dataclasses.field(default_factory=dict)  # sweep provenance
    phases: list[PhaseRecord] = dataclasses.field(default_factory=list)
    # import provenance when the report was built from a real device trace
    # (:mod:`repro.core.trace`): source frontend, trace path, clock
    # alignment, device mapping.  None for purely modeled reports.
    trace_meta: Optional[dict] = None
    # the group of the session's spans (repro.core.spans), which the
    # report's exports join; None for loaded and imported reports
    span_group: Optional[int] = dataclasses.field(default=None,
                                                  compare=False)

    # -- lazy algorithm/phase-bound views ---------------------------------
    def view(self, algorithm: Optional[str] = None,
             phase: Optional[str] = None) -> CommView:
        """The :class:`CommView` for ``(algorithm, phase)`` (defaults: the
        report's own algorithm, the whole session).  Memoized per binding;
        the default binding is seeded with the snapshot's eager artifacts,
        so reading it recomputes nothing.
        """
        alg = algorithm or self.algorithm
        cost_models.validate_algorithm(alg)
        if not hasattr(self, "_views"):
            self._views: dict = {}
        key = (alg, phase)
        if key not in self._views:
            # a sparse snapshot keeps every derived binding sparse; dense
            # snapshots leave the per-binding auto cutover in charge
            v = build_view(
                self.compiled_ops, self.num_devices, alg, self.topo,
                self.host_transfers, phase=phase,
                known_phases=self.phase_names(), label=self.name,
                sparse=True if is_sparse(self.matrix) else None,
                hlo_texts=self._all_hlo_texts())
            if phase is None and alg == self.algorithm:
                v._memo.update(matrix=self.matrix,
                               per_primitive=self.per_primitive,
                               summary=self.compiled_summary)
            self._views[key] = v
        return self._views[key]

    def _all_hlo_texts(self) -> list[str]:
        """Compiled module texts (one per capture) when the report carries
        them -- live sessions always do; loaded files only when saved with
        ``include_hlo=True``.  Empty list otherwise."""
        texts = getattr(self, "_hlo_texts", None)
        if texts:
            return [t for t in texts if t]
        single = getattr(self, "_hlo_text", None)
        return [single] if single else []

    def phase_names(self) -> list[str]:
        """Phase order of the originating session (op-tag order for files
        predating the phase records; empty for single-shot legacy data)."""
        if self.phases:
            return [p.name for p in self.phases]
        seen: list[str] = []
        for op in self.compiled_ops:
            if op.phase and op.phase not in seen:
                seen.append(op.phase)
        return seen

    def phase_view(self, phase: str,
                   algorithm: Optional[str] = None) -> CommView:
        """Shorthand for :meth:`view` with a required phase."""
        return self.view(algorithm, phase=phase)

    def phase_summaries(self, algorithm: Optional[str] = None) -> dict:
        """``{phase: Table-2 summary}`` in phase order."""
        return {p: self.view(algorithm, phase=p).summary
                for p in self.phase_names()}

    # -- paper-style renderings -------------------------------------------
    def usage_table(self) -> str:
        return reporter.primitive_usage_table(
            self.compiled_summary, title=f"{self.name}: compiled collectives")

    def logical_table(self) -> str:
        return reporter.primitive_usage_table(
            self.traced_summary, title=f"{self.name}: traced (application) collectives")

    def phase_table(self, algorithm: Optional[str] = None) -> str:
        """Per-phase Table-2 breakdown (paper Table 2, one block per
        phase) -- the session analogue of :meth:`usage_table`."""
        return reporter.phase_usage_table(
            self.phase_summaries(algorithm),
            title=f"{self.name}: per-phase compiled collectives")

    def phase_diff(self, a: str, b: str,
                   algorithm: Optional[str] = None) -> str:
        """Primitive-by-primitive comparison of two phases' compiled
        communication (calls + wire bytes, with the wire-byte delta)."""
        return reporter.phase_diff_table(
            a, self.view(algorithm, phase=a).summary,
            b, self.view(algorithm, phase=b).summary)

    def heatmap(self, kind: Optional[str] = None,
                phase: Optional[str] = None) -> str:
        v = self.view(phase=phase)
        mat = v.per_primitive.get(kind, v.matrix) if kind else v.matrix
        t = (f"{self.name} comm matrix"
             + (f" [{kind}]" if kind else "")
             + (f" [phase {phase}]" if phase else ""))
        return reporter.ascii_heatmap(mat, title=t)

    def diff(self) -> str:
        return reporter.diff_table(self.traced_summary, self.compiled_summary)

    def total_wire_bytes(self, algorithm: Optional[str] = None) -> float:
        return self.view(algorithm).total_wire_bytes()

    def collective_seconds(self, algorithm: Optional[str] = None) -> float:
        return self.view(algorithm).collective_seconds()

    def collective_seconds_split(
            self, algorithm: Optional[str] = None) -> tuple[float, float]:
        """Per-tier serialized collective time ``(ici_s, dcn_s)``; sums to
        :meth:`collective_seconds`.  ``(0, 0)`` without a topology."""
        return self.view(algorithm).collective_seconds_split()

    def collective_overlap_seconds(
            self, algorithm: Optional[str] = None) -> float:
        """Overlap-aware communication time: ICI and DCN are independent
        fabrics, so the slower tier bounds the overlapped schedule --
        ``max`` of the per-tier serialized sums, always <=
        :meth:`collective_seconds` (equal when one tier has it all)."""
        return self.view(algorithm).collective_overlap_seconds()

    # -- physical-link view ------------------------------------------------
    def link_utilization(self, algorithm: Optional[str] = None):
        """Project the matrix onto physical links (ICI hops, DCN uplinks).

        Returns a :class:`~repro.core.comm_matrix.LinkUtilization` (bytes
        per link, bottleneck link, contention-aware seconds), or ``None``
        when the report carries no topology (monitoring without
        ``mesh=``).  Derived from the compiled ops, so it works on loaded
        and cached reports too.
        """
        return self.view(algorithm).link_utilization()

    def link_matrix(self, algorithm: Optional[str] = None):
        """The ``(d+1)^2`` per-link byte matrix: entry ``(i+1, j+1)`` is the
        physical ICI link ``i -> j``; row/col 0 is the DCN tier (uplinks/
        downlinks).  ``None`` without a topology."""
        return self.view(algorithm).link_matrix()

    def link_seconds(self, algorithm: Optional[str] = None) -> float:
        """Contention-aware communication time: the bottleneck link's
        bytes/bandwidth (max over links, not flat per-chip bandwidth)."""
        return self.view(algorithm).link_seconds()

    def link_table(self) -> str:
        lu = self.link_utilization()
        if lu is None:
            return "(no topology: pass mesh= to the monitor for link stats)"
        ici_s, dcn_s = self.collective_seconds_split()
        overlap = (f"tier overlap: ici {ici_s * 1e3:.3f} ms ∥ dcn "
                   f"{dcn_s * 1e3:.3f} ms -> overlapped "
                   f"{max(ici_s, dcn_s) * 1e3:.3f} ms "
                   f"(serialized {(ici_s + dcn_s) * 1e3:.3f} ms)")
        return lu.table() + "\n" + overlap

    def render(self) -> str:
        parts = [
            f"### CommReport: {self.name} ({self.num_devices} devices) ###",
            self.logical_table(),
            self.usage_table(),
        ]
        if len(self.phase_names()) >= 2:
            parts.append(self.phase_table())
        parts += [
            "-- traced vs compiled --",
            self.diff(),
            self.heatmap(),
        ]
        if self.topo is not None:
            parts.append("-- physical links --\n" + self.link_table())
        parts.append(
            f"trace {self.trace_seconds * 1e3:.1f} ms | "
            f"compile {self.compile_seconds * 1e3:.1f} ms | "
            f"wire bytes (all devices) {reporter.human_bytes(self.total_wire_bytes())}")
        return "\n\n".join(parts)

    def rebound(self, algorithm: str) -> "CommReport":
        """A sibling snapshot report with its eager artifacts re-derived
        from ``view(algorithm)``.

        This is NOT the way to compare algorithms -- use :meth:`view`,
        which binds lazily and memoizes.  It exists for the one consumer
        that genuinely needs a whole replacement *snapshot*: the sweep
        engine's derive path, whose on-disk cache stores one serialized
        report per ``(config, mesh, algorithm)`` cell.  (The old
        ``with_algorithm`` spelling is gone; compilation never depended on
        the algorithm, so no recompilation either way.)
        """
        if algorithm == self.algorithm:
            return self
        v = self.view(algorithm)
        rep = dataclasses.replace(
            self,
            algorithm=algorithm,
            compiled_summary=v.summary,
            matrix=v.matrix,
            per_primitive=v.per_primitive,
            meta=dict(self.meta, algorithm=algorithm),
        )
        for attr in ("_lowered", "_compiled", "_hlo_text", "_hlo_texts"):
            if hasattr(self, attr):
                setattr(rep, attr, getattr(self, attr))
        return rep

    def schedule_summaries(self, algorithm: Optional[str] = None) -> list[dict]:
        """Per-op decomposition-schedule summaries (one entry per compiled
        op, aligned with ``compiled_ops``): the phase IR's serializable
        face, also written by ``save(..., include_schedules=True)``."""
        return self.view(algorithm).schedule_summaries()

    # -- measured (trace-imported) time -------------------------------------
    def measured_seconds(self, phase: Optional[str] = None) -> Optional[float]:
        """Total *measured* wall seconds over ops that carry a trace
        measurement (``op.measured_s``, schema v9) -- ``None`` when no op
        does, i.e. for purely modeled reports."""
        return self.view(phase=phase).measured_seconds()

    def compare(self, model=None, algorithm: Optional[str] = None):
        """Modeled-vs-measured comparison
        (:class:`~repro.core.trace.compare.CompareResult`) of this report's
        measured ops against ``model`` (a CommReport / CommView; default:
        this report's own modeled times)."""
        from .trace.compare import compare as compare_fn

        return compare_fn(self, model, algorithm=algorithm)

    # -- static lint ---------------------------------------------------------
    def lint(self, algorithm: Optional[str] = None,
             phase: Optional[str] = None) -> list:
        """Static anti-pattern findings
        (:class:`~repro.core.lint.LintFinding`) for the ``(algorithm,
        phase)`` binding -- lazy and memoized via :meth:`view`.  A report
        loaded from a schema-v7 file saved with ``include_lint=True``
        serves its persisted default-binding findings without re-analysis
        (and without needing the HLO text back)."""
        alg = algorithm or self.algorithm
        if phase is None and alg == self.algorithm:
            cached = getattr(self, "_lint_findings", None)
            if cached is not None:
                return cached
        return self.view(alg, phase=phase).lint()

    def lint_table(self, algorithm: Optional[str] = None) -> str:
        """Terminal rendering of :meth:`lint` (reporter.lint_table)."""
        return reporter.lint_table(
            self.lint(algorithm), title=f"{self.name}: lint findings")

    def save(self, path: str, *, include_hlo: bool = False,
             include_schedules: bool = False,
             include_lint: bool = False):
        """Write the full report as schema-v7 JSON (see ``load``).

        The file is a lossless round-trip: ops, traced events, matrices,
        summaries, topology, phase records and timings all survive.  It is
        also a strict superset of the legacy ``reporter.dump_report``
        layout (``name``, ``summary``, ``ops``, ``matrix`` keep their old
        meaning), so existing consumers of those files keep working.

        ``include_hlo=True`` additionally persists the compiled HLO text
        (gzip + base64, ``hlo_gz`` key) so :func:`roofline_of` works on the
        loaded report without a live compilation.
        ``include_schedules=True`` adds the optional schema-v5
        ``schedules`` section: one decomposition-schedule summary per op
        (phase kind / tier / structure / axis / bytes / latency hops).
        ``include_lint=True`` adds the schema-v7 ``lint`` section: the
        default binding's :meth:`lint` findings, served back by loaded
        reports without re-analysis.
        """
        from .export import export_json
        export_json(self, path, include_hlo=include_hlo,
                    include_schedules=include_schedules,
                    include_lint=include_lint)

    @classmethod
    def load(cls, path: str) -> "CommReport":
        """Read a report written by :meth:`save` (or the report cache).

        Accepts schema v1-v7.  Loaded reports render, diff, export and
        feed the cost models exactly like fresh ones; ``roofline_of``
        additionally needs the compiled HLO, which is present when the
        file was saved with ``include_hlo=True`` (otherwise a live
        compilation is required).
        """
        from .export import load_json
        return load_json(path)


def monitor_fn(
    fn,
    *args,
    mesh=None,
    name: str = "fn",
    in_shardings=None,
    out_shardings=None,
    donate_argnums=(),
    static_argnums=(),
    algorithm: str = "ring",
    host_transfers: Optional[list[HostTransfer]] = None,
    sparse: Optional[bool] = None,
    op_transform=None,
    **kwargs,
) -> CommReport:
    """Monitor one function end-to-end: a single-capture, single-phase
    :class:`~repro.core.session.MonitorSession`, snapshotted.

    ``args``/``kwargs`` may be concrete arrays or ``jax.ShapeDtypeStruct``
    stand-ins (the dry-run path: no device memory is allocated).

    ``algorithm`` selects the collective algorithm assumed by the byte
    accounting (``ring`` / ``tree`` / ``hierarchical``, paper Table 1;
    anything else raises); use ``report.view(...)`` to re-bind another one
    lazily without recompiling.  Compilation dominates this call's cost --
    for iterative use, persist the result (``report.save``) or go through
    the sweep CLI, which caches reports on disk keyed by ``(config, mesh,
    algorithm, jax version)`` and logs ``[cache] hit`` instead of
    recompiling::

        python -m repro sweep --configs paper,gnmt,resnet \\
            --algorithms ring,tree          # first run compiles
        python -m repro sweep --configs paper,gnmt,resnet \\
            --algorithms ring,tree          # second run: all cache hits

    Multi-step workloads with distinguishable phases (fwd/bwd/optimizer,
    prefill/decode) should use :class:`MonitorSession` directly -- this
    wrapper exists so one-shot callers and pre-session code keep working,
    golden-tested equal to the session path.
    """
    from .session import MonitorSession

    session = MonitorSession(mesh=mesh, name=name, algorithm=algorithm,
                             sparse=sparse)
    with session:
        session.capture(
            fn, *args, name=name,
            in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=donate_argnums, static_argnums=static_argnums,
            host_transfers=host_transfers, op_transform=op_transform,
            **kwargs)
    return session.report()


def roofline_of(report: CommReport, *, arch: str = "", mesh_name: str = "",
                model_flops: float = 0.0,
                algorithm: str = "ring") -> roofline.RooflineReport:
    assert report.topo is not None, "monitoring needs mesh= for roofline"
    # one module per capture; analyzed per module (concatenating would
    # clobber same-named computations across independently compiled modules)
    hlo_texts = getattr(report, "_hlo_texts", None)
    if not hlo_texts:
        single = getattr(report, "_hlo_text", None)
        hlo_texts = [single] if single else None
    if not hlo_texts:
        raise ValueError(
            "report carries no compiled HLO (loaded from a file saved "
            "without include_hlo=True); re-monitor, or save with "
            "report.save(path, include_hlo=True) to make rooflines work "
            "on loaded reports")
    return roofline.analyze(
        arch=arch or report.name,
        mesh_name=mesh_name,
        cost=report.cost,
        hlo_text=hlo_texts,
        topo=report.topo,
        hw=report.topo.hw if report.topo else V5E,
        model_flops=model_flops,
        memory_stats=report.memory_stats,
        algorithm=algorithm,
        link_utilization=report.link_utilization(algorithm),
    )
