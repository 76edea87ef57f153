"""The decomposition-schedule IR: one engine feeding placement, billing,
timing, links and timelines.

Property-based half (hypothesis, optional [test] extra): on single-axis
replica groups the schedule-derived matrices AND billing must equal the
legacy per-kind results for every kind x ring/tree/hierarchical.  Grid
half: multi-axis per-axis decomposition (the tentpole's new behavior) --
zero cross-axis transit inflation inside a pod, strictly reduced transit
bytes vs the flattened legacy ring, preserved Table-1 per-rank totals --
plus the IR's own invariants (tiers, streams, latency hops, schema-v5
summaries).
"""
import warnings

import numpy as np
import pytest

from repro.core import comm_matrix, cost_models
from repro.core.decompose import (CollectiveSchedule, CommPhase, decompose,
                                  group_phases)
from repro.core.events import CollectiveOp, Shape
from repro.core.topology import MeshTopology
from oracles import matrix_for_ops_reference

KINDS = ("all-reduce", "all-gather", "reduce-scatter",
         "collective-broadcast", "all-to-all")
ALGORITHMS = ("ring", "tree", "hierarchical")

ONE_AXIS = MeshTopology(axis_names=("data",), axis_sizes=(8,))
PODS_1AXIS = MeshTopology(axis_names=("pod", "data"), axis_sizes=(2, 4))
MESH_2X2X2 = MeshTopology(axis_names=("pod", "data", "model"),
                          axis_sizes=(2, 2, 2))
MESH_4X4 = MeshTopology(axis_names=("data", "model"), axis_sizes=(4, 4))
MESH_2X2X2X2 = MeshTopology(axis_names=("pod", "x", "y", "z"),
                            axis_sizes=(2, 2, 2, 2))


def mk_op(kind, elems=256, groups=None, weight=1.0):
    op = CollectiveOp(kind=kind, name="t",
                      result_shapes=[Shape("f32", (elems,))],
                      replica_groups=groups or [list(range(8))])
    op.weight = weight
    return op


def _transit_inflation(mat, topo):
    """Extra ICI bytes the link projection charges beyond the logical
    matrix's intra-pod entries: zero iff every intra-pod edge is a single
    physical neighbour hop.  (DCN edges always charge uplink+downlink, so
    they are excluded from the comparison.)"""
    lu = comm_matrix.project_links(mat, topo)
    intra = sum(mat[i + 1, j + 1]
                for i in range(topo.num_devices)
                for j in range(topo.num_devices)
                if topo.pod_index(i) == topo.pod_index(j))
    return lu.total_bytes("ici") - intra


class TestScheduleEqualsLegacyOnSingleAxis:
    """Schedule-derived placement/billing == the legacy loop wherever
    per-axis decomposition cannot apply (the retirement contract)."""

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("algorithm", ALGORITHMS)
    @pytest.mark.parametrize("topo", [None, ONE_AXIS, PODS_1AXIS],
                             ids=["none", "one_axis", "pods_1axis"])
    def test_matrix_matches_legacy(self, kind, algorithm, topo):
        op = mk_op(kind, weight=3.0)
        nd = 8
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            new = comm_matrix.matrix_for_ops([op], nd, algorithm, topo=topo)
            ref = matrix_for_ops_reference([op], nd, algorithm, topo=topo)
        if kind == "all-to-all" and algorithm == "hierarchical" \
                and topo is PODS_1AXIS:
            # hierarchical a2a now decomposes (the oracle keeps the flat
            # placement): same DCN share as flat, plus the two intra-pod
            # exchange stages; totals match the hierarchical billing.
            s, p = float(op.payload_bytes), 2
            dcn = sum(new[i + 1, j + 1] for i in range(nd)
                      for j in range(nd)
                      if topo.pod_index(i) != topo.pod_index(j))
            ref_dcn = sum(ref[i + 1, j + 1] for i in range(nd)
                          for j in range(nd)
                          if topo.pod_index(i) != topo.pod_index(j))
            assert dcn == pytest.approx(ref_dcn)
            assert dcn == pytest.approx((p - 1) / p * s * op.weight)
            assert new[1:, 1:].sum() == pytest.approx(
                cost_models.wire_bytes_group_total(
                    kind, s, nd, algorithm, pods=p) * op.weight)
            return
        np.testing.assert_allclose(new, ref, rtol=1e-12)

    @pytest.mark.parametrize("kind", KINDS + ("collective-permute",
                                              "mystery-kind"))
    @pytest.mark.parametrize("algorithm", ALGORITHMS)
    def test_per_rank_bytes_match_closed_forms(self, kind, algorithm):
        """wire_bytes_per_rank (schedule-summed) reproduces the Table-1
        closed forms for every kind x algorithm x pods."""
        s, n = 1000.0, 8
        for pods in (1, 2, 4):
            w = cost_models.wire_bytes_per_rank(kind, s, n, algorithm,
                                                pods=pods)
            p, m = (pods, n // pods) if n % pods == 0 else (1, n)
            if kind == "all-to-all":
                if algorithm == "hierarchical" and p > 1:
                    # two intra-pod exchange stages + the pod-slot DCN
                    # exchange of the S/m pod shard
                    exp = 2.0 * (m - 1) * s / (p * m * m) \
                        + (p - 1) * s / (p * p * m)
                else:
                    exp = (n - 1) * s / (n * n)
            elif kind in ("collective-permute", "mystery-kind"):
                exp = s
            elif kind == "all-reduce":
                if algorithm == "tree":
                    exp = 2.0 * s
                elif algorithm == "hierarchical" and p > 1:
                    exp = 2.0 * (m - 1) * s / m + 2.0 * (p - 1) * s / n
                else:
                    exp = 2.0 * (n - 1) * s / n
            else:   # one-phase kinds
                if algorithm == "hierarchical" and p > 1:
                    exp = (m - 1) * s / m + (p - 1) * s / n
                else:
                    exp = (n - 1) * s / n
            assert w == pytest.approx(exp), (kind, algorithm, pods)

    @pytest.mark.parametrize("algorithm", ALGORITHMS)
    def test_time_split_reads_the_same_schedule(self, algorithm):
        """collective_time_split == the schedule's own time_split."""
        op = mk_op("all-reduce")
        sched = decompose(op, algorithm, MESH_2X2X2, warn=False)
        assert cost_models.collective_time_split(
            op, MESH_2X2X2, algorithm) == sched.time_split(MESH_2X2X2)
        assert cost_models.collective_time_split(
            op, MESH_2X2X2, algorithm, include_latency=False) == \
            sched.time_split(MESH_2X2X2, include_latency=False)


try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False


if _HAVE_HYPOTHESIS:
    @st.composite
    def single_axis_ops(draw):
        """Randomized op streams whose groups partition a single-axis
        8-ring -- the domain where schedule == legacy is exact."""
        ops = []
        for _ in range(draw(st.integers(1, 6))):
            kind = draw(st.sampled_from(KINDS))
            elems = draw(st.integers(1, 2048))
            gsize = draw(st.sampled_from([2, 4, 8]))
            devs = draw(st.permutations(range(8)))
            groups = [sorted(devs[i:i + gsize])
                      for i in range(0, 8, gsize)]
            op = mk_op(kind, elems=elems, groups=groups,
                       weight=float(draw(st.integers(1, 64))))
            ops.append(op)
        return ops

    class TestScheduleLegacyProperty:
        """Satellite: hypothesis property pinning schedule-derived
        matrices AND billing equal to the legacy single-axis results for
        all kinds x ring/tree/hierarchical."""

        @given(ops=single_axis_ops(), algorithm=st.sampled_from(ALGORITHMS))
        @settings(max_examples=60, deadline=None)
        def test_matrices_and_billing_match_legacy(self, ops, algorithm):
            for topo in (None, ONE_AXIS, PODS_1AXIS):
                # as in test_matrix_matches_legacy: hierarchical a2a on the
                # two-pod mesh relays through pod leaders, which the legacy
                # oracle never modeled -- only its row sums are compared
                same = [op for op in ops if not (
                    op.kind == "all-to-all" and algorithm == "hierarchical"
                    and topo is PODS_1AXIS)]
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    new = comm_matrix.matrix_for_ops(same, 8, algorithm,
                                                     topo=topo)
                    ref = matrix_for_ops_reference(
                        same, 8, algorithm, topo=topo)
                    np.testing.assert_allclose(new, ref, rtol=1e-12)
                    # billing: row sums == device model x weight, per op
                    for op in ops:
                        mat = comm_matrix.matrix_for_ops([op], 8,
                                                         algorithm,
                                                         topo=topo)
                        rows = mat[1:, 1:].sum(axis=1)
                        for g in op.replica_groups:
                            exp = cost_models.device_send_bytes(
                                op.kind, op.payload_bytes, g, algorithm,
                                topo=topo)
                            for d in g:
                                assert rows[d] == pytest.approx(
                                    exp[d] * op.weight)


class TestPerAxisDecomposition:
    """The tentpole's new placement: ring per torus axis instead of the
    flattened ring."""

    @pytest.mark.parametrize("kind", ("all-reduce", "all-gather",
                                      "reduce-scatter",
                                      "collective-broadcast"))
    def test_zero_transit_inflation_inside_pod(self, kind):
        """Acceptance criterion: a multi-axis group's link matrix shows
        zero cross-axis transit inflation inside a pod -- every placed
        edge is a physical neighbour hop."""
        op = mk_op(kind, groups=[list(range(16))])
        mat = comm_matrix.matrix_for_ops([op], 16, "ring", topo=MESH_4X4)
        assert _transit_inflation(mat, MESH_4X4) == pytest.approx(0.0)

    @pytest.mark.parametrize("kind", ("all-reduce", "all-gather"))
    def test_strictly_reduces_intra_pod_transit_bytes(self, kind):
        """Satellite: per-axis decomposition strictly reduces intra-pod
        transit bytes vs the legacy flattened ring."""
        op = mk_op(kind, groups=[list(range(16))])
        new = comm_matrix.matrix_for_ops([op], 16, "ring", topo=MESH_4X4)
        ref = matrix_for_ops_reference([op], 16, "ring", topo=MESH_4X4)
        assert _transit_inflation(ref, MESH_4X4) > 0, \
            "legacy flattened ring must show transit inflation on 4x4"
        assert _transit_inflation(new, MESH_4X4) < \
            _transit_inflation(ref, MESH_4X4)
        assert _transit_inflation(new, MESH_4X4) == pytest.approx(0.0)

    @pytest.mark.parametrize("kind", ("all-reduce", "all-gather",
                                      "reduce-scatter",
                                      "collective-broadcast"))
    def test_per_rank_totals_preserved(self, kind):
        """Per-axis phases move the same Table-1 per-rank bytes as the
        flattened ring -- only *where* they travel changes."""
        op = mk_op(kind, groups=[list(range(16))])
        mat = comm_matrix.matrix_for_ops([op], 16, "ring", topo=MESH_4X4)
        per_rank = cost_models.wire_bytes_per_rank(
            kind, op.payload_bytes, 16, "ring")
        for d in range(16):
            assert mat[d + 1, 1:].sum() == pytest.approx(per_rank)

    def test_hierarchical_intra_pod_goes_per_axis(self):
        """Acceptance criterion: the hierarchical intra-pod phases decompose
        per axis too -- zero ICI transit inflation, same DCN share."""
        op = mk_op("all-reduce", groups=[list(range(8))])
        new = comm_matrix.matrix_for_ops([op], 8, "hierarchical",
                                         topo=MESH_2X2X2)
        ref = matrix_for_ops_reference(
            [op], 8, "hierarchical", topo=MESH_2X2X2)
        assert _transit_inflation(new, MESH_2X2X2) == pytest.approx(0.0)
        # DCN bytes (the shard exchange) are identical to the legacy split
        def cross(m):
            return sum(m[i + 1, j + 1] for i in range(8) for j in range(8)
                       if MESH_2X2X2.pod_index(i)
                       != MESH_2X2X2.pod_index(j))
        assert cross(new) == pytest.approx(cross(ref))
        # and per-rank totals survive
        per_rank = cost_models.wire_bytes_per_rank(
            "all-reduce", op.payload_bytes, 8, "hierarchical", pods=2)
        for d in range(8):
            assert new[d + 1, 1:].sum() == pytest.approx(per_rank)

    def test_three_axis_group_decomposes_fully(self):
        op = mk_op("all-reduce", groups=[list(range(8))])
        sched = decompose(op, "ring", MESH_2X2X2X2)
        axes = [ph.axis for ph in sched.phases]
        assert axes == ["z", "y", "x", "x", "y", "z"]   # RS down, AG up
        assert all(ph.tier == "ici" for ph in sched.phases)
        mat = comm_matrix.matrix_for_ops([op], 16, "ring",
                                         topo=MESH_2X2X2X2)
        assert _transit_inflation(mat, MESH_2X2X2X2) == pytest.approx(0.0)

    def test_partial_axis_group_stays_flattened(self):
        """A group that is NOT a full-axis product (a strided subset) keeps
        the flattened ring -- no invented per-axis structure."""
        op = mk_op("all-reduce", groups=[[0, 1, 4, 5]])   # x fixed? no: 2
        sched = decompose(op, "ring", MESH_4X4)
        assert [ph.axis for ph in sched.phases] == ["", ""]

    def test_single_axis_group_keeps_flattened_ring(self):
        """Single-axis groups keep the (identical) flattened ring so the
        legacy oracle stays byte-exact on them."""
        op = mk_op("all-reduce", groups=[[0, 4, 8, 12]])  # one model column
        sched = decompose(op, "ring", MESH_4X4)
        assert [ph.axis for ph in sched.phases] == ["", ""]

    def test_crossing_groups_never_decompose_per_axis(self):
        """A ring group spanning pods stays a flat DCN-billed ring: the
        paper-faithful distinction from hierarchical is preserved."""
        op = mk_op("all-reduce", groups=[list(range(8))])
        sched = decompose(op, "ring", MESH_2X2X2)
        assert {ph.tier for ph in sched.phases} == {"dcn"}
        assert [ph.axis for ph in sched.phases] == ["", ""]


class TestScheduleIR:
    """The IR's own contracts: structure, streams, summaries."""

    def test_permute_pairs_split_by_tier(self):
        """A collective-permute's pairs are billed where they travel:
        cross-pod pairs on DCN, intra-pod pairs on ICI, as concurrent
        streams -- timing and link projection agree."""
        op = CollectiveOp(kind="collective-permute", name="p",
                          result_shapes=[Shape("f32", (1024,))],
                          replica_groups=[],
                          source_target_pairs=[(0, 4), (4, 0), (1, 2)])
        sched = decompose(op, "ring", PODS_1AXIS)
        tiers = {ph.tier: ph for ph in sched.phases}
        assert set(tiers) == {"ici", "dcn"}
        assert len(tiers["dcn"].pairs) == 2 and len(tiers["ici"].pairs) == 1
        assert tiers["ici"].stream != tiers["dcn"].stream
        s = float(op.result_bytes)
        ici, dcn = cost_models.collective_time_split(
            op, PODS_1AXIS, "ring", include_latency=False)
        assert dcn == pytest.approx(s / PODS_1AXIS.ring_bw_per_chip(True))
        assert ici == pytest.approx(s / PODS_1AXIS.ring_bw_per_chip(False))
        lu = comm_matrix.link_utilization_for_ops([op], PODS_1AXIS)
        assert lu.total_bytes("dcn") > 0 and lu.total_bytes("ici") > 0
        # single-pod (or no topo): everything stays one ICI phase
        flat = decompose(op, "ring", None)
        assert [ph.tier for ph in flat.phases] == ["ici"]

    def test_hierarchical_schedule_shape(self):
        op = mk_op("all-reduce", groups=[list(range(8))])
        sched = decompose(op, "hierarchical", MESH_2X2X2)
        kinds = [(ph.kind, ph.tier) for ph in sched.phases]
        # per-axis RS inside the pod, DCN shard all-reduce, per-axis AG
        assert kinds == [("reduce-scatter", "ici"), ("reduce-scatter", "ici"),
                        ("all-reduce", "dcn"),
                        ("all-gather", "ici"), ("all-gather", "ici")]
        dcn = [ph for ph in sched.phases if ph.tier == "dcn"]
        assert dcn[0].bytes_per_rank == pytest.approx(
            2 * (2 - 1) * op.payload_bytes / 8)

    def test_streams_are_concurrent_groups(self):
        """Disjoint replica groups land on distinct streams; time is the
        max over streams, not the sum."""
        op = mk_op("all-reduce", groups=[[0, 1], [2, 3, 4, 5]])
        sched = decompose(op, "ring", ONE_AXIS)
        streams = {ph.stream for ph in sched.phases}
        assert len(streams) == 2
        ici, dcn = sched.time_split(ONE_AXIS, include_latency=False)
        s = float(op.payload_bytes)
        slowest = max(2 * (2 - 1) * s / 2, 2 * (4 - 1) * s / 4) \
            / ONE_AXIS.ring_bw_per_chip(False)
        assert ici == pytest.approx(slowest) and dcn == 0.0

    def test_batched_groups_share_phases(self):
        """Same-size groups batch into shared phases (the vectorized
        builder's fast path) without changing the placed traffic."""
        op = mk_op("all-gather", groups=[[0, 1, 2, 3], [4, 5, 6, 7]])
        sched = decompose(op, "ring", None)
        assert len(sched.phases) == 1
        assert sched.phases[0].groups.shape == (2, 4)

    def test_summary_is_serializable(self):
        import json
        op = mk_op("all-reduce", groups=[list(range(8))])
        sched = decompose(op, "hierarchical", MESH_2X2X2)
        doc = sched.summary()
        json.dumps(doc)
        assert doc["kind"] == "all-reduce"
        assert {ph["tier"] for ph in doc["phases"]} == {"ici", "dcn"}
        assert all({"kind", "tier", "structure", "axis", "num_groups",
                    "group_size", "bytes_per_rank", "latency_hops"}
                   <= set(ph) for ph in doc["phases"])

    def test_total_bytes_matches_wire_total(self):
        for kind in KINDS:
            for alg in ALGORITHMS:
                op = mk_op(kind)
                sched = decompose(op, alg, None)
                assert sched.total_bytes() * op.weight == pytest.approx(
                    op.wire_bytes_total(alg)), (kind, alg)

    def test_group_phases_is_abstract_decompose(self):
        """group_phases with pods= reproduces the concrete decomposition's
        byte amounts without a mesh (the Table-1 entry point)."""
        abstract = group_phases("all-reduce", 1024.0, range(8),
                                "hierarchical", pods=2, warn=False)
        concrete = decompose(mk_op("all-reduce", elems=256,
                                   groups=[list(range(8))]),
                             "hierarchical", PODS_1AXIS).phases
        assert [round(p.bytes_per_rank, 9) for p in abstract] == \
            [round(p.bytes_per_rank, 9) for p in concrete]
        assert [p.tier for p in abstract] == [p.tier for p in concrete]


class TestScheduleSerialization:
    """Schema v5: optional per-op schedule summaries ride with reports."""

    def _report(self):
        from repro.core import CommReport, hlo_parser
        op = mk_op("all-reduce", groups=[list(range(8))])
        return CommReport(
            name="sched", num_devices=8, traced=[], compiled_ops=[op],
            traced_summary={},
            compiled_summary=hlo_parser.summarize([op], "hierarchical",
                                                  topo=MESH_2X2X2),
            matrix=comm_matrix.matrix_for_ops([op], 8, "hierarchical",
                                              topo=MESH_2X2X2),
            per_primitive={}, cost={}, memory_stats=None,
            trace_seconds=0.0, compile_seconds=0.0, topo=MESH_2X2X2,
            algorithm="hierarchical")

    def test_schedules_written_on_request(self, tmp_path):
        import json
        rep = self._report()
        p = str(tmp_path / "s.json")
        rep.save(p, include_schedules=True)
        d = json.loads(open(p).read())
        assert d["schema"] == "repro.comm_report.v9"
        assert len(d["schedules"]) == 1
        assert {ph["tier"] for ph in d["schedules"][0]["phases"]} == \
            {"ici", "dcn"}

    def test_schedules_absent_by_default_and_rederivable(self, tmp_path):
        import json
        rep = self._report()
        p = str(tmp_path / "s.json")
        rep.save(p)
        d = json.loads(open(p).read())
        assert "schedules" not in d
        from repro.core import CommReport
        back = CommReport.load(p)
        assert back.schedule_summaries() == rep.schedule_summaries()

    def test_v4_files_still_load(self, tmp_path):
        import json
        from repro.core import CommReport
        rep = self._report()
        p = str(tmp_path / "old.json")
        rep.save(p)
        d = json.loads(open(p).read())
        d["schema"] = "repro.comm_report.v4"
        with open(p, "w") as f:
            json.dump(d, f)
        back = CommReport.load(p)
        np.testing.assert_allclose(back.matrix, rep.matrix)
