"""Lazy, memoized algorithm-bound views of a collective-op stream.

A :class:`CommView` owns ONE ``(algorithm, topology)`` binding of a set of
compiled ops and every artifact derived from it -- the ``(d+1)^2`` matrix,
per-primitive matrices, the Table-2/3 summary, link utilization, per-tier
collective seconds, overlap bounds, roofline inputs.  Each artifact is
computed on first access and memoized, so consumers stop threading
``algorithm=None, topo=...`` through every call: bind once, read many.

Re-binding is free until read: ``view.rebind("tree")`` shares the same op
list and recomputes nothing until an artifact is touched -- the cheap way
to compare ring vs tree vs hierarchical for one program (no recompilation,
no eager ``dataclasses.replace`` churn).

Views are produced by :meth:`repro.core.session.MonitorSession.view`
(whole-session or per-phase) and :meth:`repro.core.monitor.CommReport.view`
(including loaded/cached reports); building one directly from a plain op
list works too.
"""
from __future__ import annotations

from typing import Iterable, Optional

from . import comm_matrix, cost_models, hlo_parser, spans
from .decompose import schedules_for_ops
from .events import CollectiveOp, HostTransfer
from .sparse import SPARSE_DEVICE_THRESHOLD
from .topology import MeshTopology


def build_view(ops, num_devices: int, algorithm: str,
               topo: Optional[MeshTopology], host_transfers,
               *, phase: Optional[str], known_phases, label: str,
               sparse: Optional[bool] = None, hlo_texts=()):
    """Construct the :class:`CommView` for one ``(algorithm, phase)``
    binding -- the shared filter/validation behind both
    ``MonitorSession.view`` and ``CommReport.view`` (one implementation,
    so session and snapshot views cannot diverge).

    ``phase=None`` binds everything; a named phase filters ops and host
    transfers by their tag and must be one of ``known_phases``.
    ``sparse`` is the matrix-representation mode (None = auto by device
    count, see :class:`CommView`).  ``hlo_texts`` are the captures'
    compiled modules (one string each) -- the def-use ground truth the
    :meth:`CommView.lint` rules read; views without them still lint, on
    the schedule-only rules.
    """
    if phase is not None:
        known = list(known_phases)
        if phase not in known:
            raise KeyError(
                f"unknown phase {phase!r}; known phases: {known}")
        ops = [op for op in ops if op.phase == phase]
        host_transfers = [t for t in host_transfers if t.phase == phase]
    return CommView(ops, num_devices, algorithm=algorithm, topo=topo,
                    host_transfers=host_transfers,
                    label=f"{label}:{phase or 'all'}", sparse=sparse,
                    hlo_texts=hlo_texts)


class CommView:
    """One ``(ops, algorithm, topology)`` binding; every derived artifact
    lazy and memoized.

    The view never mutates its inputs: ``rebind`` shares the same op list
    under a different algorithm with a fresh memo, and the memoized arrays
    are handed out by reference (treat them as read-only).
    """

    def __init__(self, ops: Iterable[CollectiveOp], num_devices: int, *,
                 algorithm: str = "ring",
                 topo: Optional[MeshTopology] = None,
                 host_transfers: Iterable[HostTransfer] = (),
                 label: str = "", sparse: Optional[bool] = None,
                 hlo_texts: Iterable[str] = ()):
        cost_models.validate_algorithm(algorithm)
        self.ops = list(ops)
        self.num_devices = int(num_devices)
        self.algorithm = algorithm
        self.topo = topo
        self.host_transfers = list(host_transfers)
        self.label = label
        # compiled module text per capture -- def-use input for lint()
        self.hlo_texts = [t for t in hlo_texts if t]
        # matrix representation: True = COO SparseCommMatrix, False =
        # dense ndarray, None = auto (sparse above the device-count
        # cutover -- the dense array is O(d^2) memory)
        self.sparse = sparse
        self._memo: dict = {}
        # id(schedule) -> its edge arrays, shared by the matrix build and
        # every per-primitive slice (schedules() keeps the keys alive)
        self._edges: dict = {}

    @property
    def use_sparse(self) -> bool:
        """The resolved matrix representation for this view."""
        if self.sparse is None:
            return self.num_devices > SPARSE_DEVICE_THRESHOLD
        return bool(self.sparse)

    def __repr__(self) -> str:
        tag = f" {self.label!r}" if self.label else ""
        return (f"CommView({len(self.ops)} ops, {self.num_devices} devices, "
                f"algorithm={self.algorithm!r}{tag})")

    def _cached(self, key: str, build):
        if key not in self._memo:
            self._memo[key] = build()
        return self._memo[key]

    def rebind(self, algorithm: str) -> "CommView":
        """Same ops/topology under another algorithm (fresh memo, no
        recompilation -- compilation never depended on the algorithm)."""
        if algorithm == self.algorithm:
            return self
        return CommView(self.ops, self.num_devices, algorithm=algorithm,
                        topo=self.topo, host_transfers=self.host_transfers,
                        label=self.label, sparse=self.sparse,
                        hlo_texts=self.hlo_texts)

    # -- byte accounting ---------------------------------------------------
    @property
    def matrix(self):
        """``(d+1)^2`` bytes-sent matrix (host transfers in row/col 0).

        A dense ``np.ndarray`` or, when :attr:`use_sparse` resolves true,
        the byte-identical COO :class:`~repro.core.sparse.
        SparseCommMatrix` -- every downstream consumer (link projection,
        heatmaps, exporters) accepts both.
        """
        def build():
            with spans.span("view.matrix"):
                mat = comm_matrix.matrix_for_schedules(
                    self.ops, self.schedules(), self.num_devices,
                    sparse=self.use_sparse, edge_cache=self._edges)
                self._count_edges()
                if self.host_transfers:
                    comm_matrix.add_host_transfers(mat, self.host_transfers)
            return mat
        return self._cached("matrix", build)

    @property
    def per_primitive(self) -> dict:
        """Paper Fig. 3: one matrix per collective primitive."""
        def build():
            with spans.span("view.per_primitive"):
                scheds = self.schedules()
                out = {k: comm_matrix.matrix_for_schedules(
                           self.ops, scheds, self.num_devices, kinds={k},
                           sparse=self.use_sparse, edge_cache=self._edges)
                       for k in sorted({op.kind for op in self.ops})}
                self._count_edges()
                return out
        return self._cached("per_primitive", build)

    def _count_edges(self) -> None:
        """Counter ``view.edges`` of the open span: the edges the matrix
        build just placed, one per (source, destination) entry of each
        op's schedule, repeats included."""
        spans.count("view.edges", sum(
            self._edges[id(s)][0].size for s in self.schedules()))

    @property
    def summary(self) -> dict:
        """Paper Table-2/3 per-kind calls / payload / wire bytes."""
        return self._cached("summary", lambda: hlo_parser.summarize(
            self.ops, self.algorithm, topo=self.topo))

    def total_wire_bytes(self) -> float:
        """Global bytes-on-the-wire across all devices."""
        return self._cached("total_wire_bytes", lambda: (
            hlo_parser.total_wire_bytes(self.ops, self.algorithm,
                                        topo=self.topo)))

    # -- decomposition schedules -------------------------------------------
    def schedules(self) -> list:
        """One :class:`~repro.core.decompose.CollectiveSchedule` per op
        (aligned with ``self.ops``) -- the phase IR every derived artifact
        reads.  Deduped by op signature (``decompose`` runs once per
        *distinct shape*, and ops sharing a signature share one schedule
        object), memoized, and built with fallback warnings on, like the
        placement always warned."""
        def build():
            with spans.span("view.schedule"):
                scheds = schedules_for_ops(self.ops, self.algorithm,
                                           self.topo, warn=True)
                spans.count("view.shapes", len({id(s) for s in scheds}))
                return scheds
        return self._cached("schedules", build)

    def schedule_summaries(self) -> list[dict]:
        """Serializable per-op schedule summaries (schema-v5 section)."""
        return [sched.summary() for sched in self.schedules()]

    # -- time models -------------------------------------------------------
    def collective_seconds(self) -> float:
        """Serialized collective time (0.0 without a topology)."""
        ici, dcn = self.collective_seconds_split()
        return ici + dcn

    def collective_seconds_split(self) -> tuple[float, float]:
        """Per-tier serialized collective time ``(ici_s, dcn_s)``,
        execution-weighted, summed in op order."""
        def build():
            if self.topo is None:
                return 0.0, 0.0
            return cost_models.weighted_time_split(self.ops,
                                                   self._time_splits())
        return self._cached("seconds_split", build)

    def collective_overlap_seconds(self) -> float:
        """Tier-overlapped communication time: ``max(ici_s, dcn_s)``."""
        return max(self.collective_seconds_split())

    def _time_splits(self) -> list:
        """``(ici_s, dcn_s)`` of one execution per op, each distinct
        schedule timed once."""
        return self._cached("time_splits", lambda: cost_models.time_splits(
            self.schedules(), self.topo))

    def op_seconds(self) -> list:
        """Modeled seconds per op (aligned with ``self.ops``): each entry
        is the op's serialized schedule time -- ``sum(time_split)`` --
        times its execution weight.  ``None`` entries without a topology
        (no time model); the compare layer matches these against the
        measured ``op.measured_s`` values a trace import carries."""
        def build():
            if self.topo is None:
                return [None] * len(self.ops)
            return [(ici + dcn) * max(1.0, op.weight)
                    for op, (ici, dcn) in zip(self.ops, self._time_splits())]
        return self._cached("op_seconds", build)

    def measured_seconds(self):
        """Total measured wall seconds over ops carrying ``measured_s``
        (trace imports, schema v9); ``None`` when no op is measured."""
        vals = [op.measured_s for op in self.ops
                if op.measured_s is not None]
        return float(sum(vals)) if vals else None

    # -- physical-link view ------------------------------------------------
    def link_utilization(self):
        """Per-physical-link byte counts (None without a topology)."""
        def build():
            if self.topo is None:
                return None
            return comm_matrix.project_links(self.matrix, self.topo)
        return self._cached("link_utilization", build)

    def link_matrix(self):
        lu = self.link_utilization()
        return None if lu is None else lu.matrix()

    def link_seconds(self) -> float:
        """Contention-aware bound: the bottleneck link's bytes/bandwidth."""
        lu = self.link_utilization()
        return 0.0 if lu is None else lu.bottleneck_seconds()

    # -- static lint ---------------------------------------------------------
    def lint(self) -> list:
        """Static anti-pattern findings for this binding (lazy, memoized
        like every other artifact): a list of
        :class:`~repro.core.lint.LintFinding`, errors first, then by
        modeled savings.  HLO def-use rules run only when the view carries
        :attr:`hlo_texts`; schedule rules always run (savings are zero
        without a topology)."""
        from .lint import lint_ops   # deferred: lint imports decompose

        return self._cached("lint", lambda: lint_ops(
            self.ops, topo=self.topo, algorithm=self.algorithm,
            hlo_texts=self.hlo_texts))
