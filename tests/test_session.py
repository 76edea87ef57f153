"""Session API: multi-phase capture, lazy CommView bindings, schema v4, and
the monitor_fn compatibility contract (golden equality with a single-phase
session)."""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (CommReport, CommView, MonitorSession, comm_matrix,
                        hlo_parser, monitor_fn, roofline_of)
from repro.core.events import CollectiveOp, HostTransfer, Shape


def mk_op(kind="all-reduce", elems=64, groups=None, pairs=None, phase=""):
    return CollectiveOp(kind=kind, name="t",
                        result_shapes=[Shape("f32", (elems,))],
                        replica_groups=groups or [[0, 1, 2, 3]],
                        source_target_pairs=pairs or [], phase=phase)


class TestCommView:
    """The lazy view: memoized artifacts, cheap re-binding, validation."""

    def test_matches_functional_layer(self):
        ops = [mk_op("all-reduce"), mk_op("all-gather", groups=[[0, 1]])]
        v = CommView(ops, 4)
        np.testing.assert_allclose(
            v.matrix, comm_matrix.matrix_for_ops(ops, 4))
        assert v.summary == hlo_parser.summarize(ops)
        assert v.total_wire_bytes() == hlo_parser.total_wire_bytes(ops)
        assert set(v.per_primitive) == {"all-reduce", "all-gather"}

    def test_memoized(self):
        v = CommView([mk_op()], 4)
        assert v.matrix is v.matrix
        assert v.per_primitive is v.per_primitive
        assert v.summary is v.summary

    def test_rebind_is_lazy_and_shares_ops(self):
        v = CommView([mk_op()], 4)
        _ = v.matrix
        t = v.rebind("tree")
        assert t.ops == v.ops and t.ops[0] is v.ops[0]
        assert not t._memo, "rebinding must not compute anything eagerly"
        assert not np.allclose(t.matrix, v.matrix)
        assert v.rebind("ring") is v

    def test_host_transfers_in_matrix(self):
        v = CommView([mk_op()], 4,
                     host_transfers=[HostTransfer("h2d", 1, 512)])
        assert v.matrix[0, 2] == 512

    def test_no_topo_degenerates(self):
        v = CommView([mk_op()], 4)
        assert v.link_utilization() is None
        assert v.link_matrix() is None
        assert v.collective_seconds() == 0.0
        assert v.link_seconds() == 0.0

    def test_unknown_algorithm_rejected(self):
        with pytest.raises(ValueError, match="unknown algorithm"):
            CommView([mk_op()], 4, algorithm="nccl")


class TestAlgorithmValidation:
    """Satellite: every entry point rejects unknown algorithm strings."""

    def test_session_ctor(self):
        with pytest.raises(ValueError, match="unknown algorithm"):
            MonitorSession(algorithm="treee")

    def test_session_view(self):
        with pytest.raises(ValueError, match="unknown algorithm"):
            MonitorSession().view("treee")

    def test_matrix_for_ops(self):
        with pytest.raises(ValueError, match="unknown algorithm"):
            comm_matrix.matrix_for_ops([mk_op()], 4, "collnet")

    def test_report_view(self):
        rep = _hand_report()
        with pytest.raises(ValueError, match="unknown algorithm"):
            rep.view("nccl")

    @pytest.mark.compile
    def test_monitor_fn(self, mesh8):
        with pytest.raises(ValueError, match="unknown algorithm"):
            monitor_fn(lambda x: x.sum(),
                       jax.ShapeDtypeStruct((8,), jnp.float32),
                       mesh=mesh8, algorithm="treee")


class TestPermuteNumGroups:
    """Satellite: multi-group collective-permutes scale like every other
    kind (wire totals AND matrix placement)."""

    def test_wire_bytes_scale_with_groups(self):
        pairs = [(0, 1), (1, 0)]
        one = mk_op("collective-permute", groups=[[0, 1]], pairs=pairs)
        two = mk_op("collective-permute", groups=[[0, 1], [2, 3]],
                    pairs=pairs)
        assert two.num_groups == 2
        assert two.wire_bytes_total() == 2 * one.wire_bytes_total()

    def test_matrix_total_matches_wire_total(self):
        op = mk_op("collective-permute", groups=[[0, 1], [2, 3]],
                   pairs=[(0, 1), (1, 0)])
        mat = comm_matrix.matrix_for_ops([op], 4)
        assert mat.sum() == pytest.approx(op.wire_bytes_total())

    def test_groupless_permute_unchanged(self):
        op = mk_op("collective-permute", groups=[], pairs=[(0, 1)])
        assert op.wire_bytes_total() == op.result_bytes


def _hand_report(phases=()):
    ops = [mk_op(phase=p) for p in (phases or ("",))]
    from repro.core.events import PhaseRecord
    v = CommView(ops, 4)
    return CommReport(
        name="hand", num_devices=4, traced=[], compiled_ops=ops,
        traced_summary={}, compiled_summary=v.summary, matrix=v.matrix,
        per_primitive=v.per_primitive, cost={}, memory_stats=None,
        trace_seconds=0.0, compile_seconds=0.0,
        phases=[PhaseRecord(name=p, num_captures=1) for p in phases])


class TestReportPhases:
    """Phase bookkeeping on hand-built reports (no compilation)."""

    def test_phase_names_from_records(self):
        rep = _hand_report(phases=("fwd", "bwd"))
        assert rep.phase_names() == ["fwd", "bwd"]

    def test_phase_names_from_op_tags_when_no_records(self):
        rep = _hand_report()
        rep.compiled_ops[0].phase = "legacy"
        rep.phases = []
        assert rep.phase_names() == ["legacy"]

    def test_unknown_phase_rejected(self):
        rep = _hand_report(phases=("fwd",))
        with pytest.raises(KeyError, match="unknown phase"):
            rep.view(phase="bwd")

    def test_phase_view_filters_ops(self):
        rep = _hand_report(phases=("fwd", "bwd"))
        v = rep.view(phase="fwd")
        assert all(op.phase == "fwd" for op in v.ops)
        assert len(v.ops) == 1

    def test_default_view_seeded_from_snapshot(self):
        rep = _hand_report(phases=("fwd",))
        assert rep.view().matrix is rep.matrix
        assert rep.view().summary is rep.compiled_summary

    def test_phase_table_marks_empty_phase(self):
        rep = _hand_report(phases=("fwd",))
        from repro.core.events import PhaseRecord
        rep.phases.append(PhaseRecord(name="optim", num_captures=1))
        txt = rep.phase_table()
        assert "optim" in txt and "(none)" in txt

    def test_phase_diff_renders_delta(self):
        rep = _hand_report(phases=("fwd", "bwd"))
        txt = rep.phase_diff("fwd", "bwd")
        assert "fwd calls" in txt and "bwd wire" in txt and "Δ wire" in txt


@pytest.fixture(scope="module")
def phased_session(mesh8):
    """fwd / bwd / optim phases: fwd + bwd communicate, optim is local."""
    ws = NamedSharding(mesh8, P(None, "model"))
    xs = NamedSharding(mesh8, P("data", None))
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)

    def fwd(w, x):
        return ((x @ w) ** 2).mean()

    def optim(w):
        return w * 0.9

    sess = MonitorSession(mesh=mesh8, name="phased")
    with sess:
        with sess.phase("fwd"):
            sess.capture(fwd, w, x, in_shardings=(ws, xs))
        with sess.phase("bwd"):
            sess.capture(jax.value_and_grad(fwd), w, x,
                         in_shardings=(ws, xs))
        with sess.phase("optim"):
            sess.capture(optim, w, in_shardings=(ws,))
    return sess


class TestHardwareOfMesh:
    """A session's topology takes the chip's spec from its device_kind."""

    @staticmethod
    def _mesh(platform, kind):
        import types
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        return types.SimpleNamespace(devices=np.array([dev, dev]),
                                     axis_names=("data",))

    def test_v5e_kind_maps_to_v5e(self):
        from repro.core import MeshTopology, V5E
        topo = MeshTopology.from_mesh(self._mesh("tpu", "TPU v5 lite"))
        assert topo.hw is V5E and topo.axis_sizes == (2,)

    def test_unknown_tpu_kind_is_an_error(self):
        from repro.core import MeshTopology
        with pytest.raises(ValueError, match="TPU v9"):
            MeshTopology.from_mesh(self._mesh("tpu", "TPU v9"))

    def test_gpu_mesh_without_a_spec_is_an_error(self):
        """Only CPU dry-run meshes model a v5e; a GPU is never given TPU
        link and peak constants."""
        from repro.core import MeshTopology
        with pytest.raises(ValueError, match="gpu device kind 'NVIDIA H100'"):
            MeshTopology.from_mesh(self._mesh("gpu", "NVIDIA H100"))

    def test_cpu_mesh_models_v5e(self, mesh8):
        from repro.core import MeshTopology, V5E
        assert MeshTopology.from_mesh(mesh8).hw is V5E


@pytest.mark.compile
class TestMonitorSession:
    def test_phase_order_and_records(self, phased_session):
        sess = phased_session
        assert sess.phase_names() == ["fwd", "bwd", "optim"]
        assert all(sess._phases[p].num_captures == 1
                   for p in sess.phase_names())
        assert sess.compile_seconds > 0

    def test_jitted_fn_captured_as_is_after_it_ran(self, mesh8):
        """A jitted function the caller already ran is lowered as it is,
        and its cached trace does not hide its psum from the interceptor."""
        f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "data"),
                                  mesh=mesh8, in_specs=P("data"),
                                  out_specs=P()))
        x = jnp.ones((8, 4))
        f(x)
        sess = MonitorSession(mesh=mesh8)
        cap = sess.capture(f, jax.ShapeDtypeStruct(x.shape, x.dtype))
        assert [ev.primitive for ev in cap.traced] == ["psum"]
        assert [op.kind for op in cap.ops] == ["all-reduce"]
        assert sess.last_lowered.as_text() == f.lower(x).as_text()
        np.testing.assert_array_equal(f(x), jnp.full((2, 4), 4.0))

    def test_ops_are_phase_tagged(self, phased_session):
        phases = {op.phase for op in phased_session.compiled_ops}
        assert phases <= {"fwd", "bwd", "optim"}
        assert "bwd" in phases

    def test_per_phase_sums_equal_whole(self, phased_session):
        sess = phased_session
        total = sum(sess.view(phase=p).matrix for p in sess.phase_names())
        np.testing.assert_allclose(total, sess.view().matrix)
        whole = sess.view().summary
        per = {}
        for p in sess.phase_names():
            for kind, row in sess.view(phase=p).summary.items():
                agg = per.setdefault(kind, {"calls": 0, "wire_bytes": 0.0})
                agg["calls"] += row["calls"]
                agg["wire_bytes"] += row["wire_bytes"]
        for kind, row in whole.items():
            assert per[kind]["calls"] == row["calls"]
            assert per[kind]["wire_bytes"] == pytest.approx(
                row["wire_bytes"])

    def test_rebinding_recompiles_nothing(self, phased_session):
        sess = phased_session
        n_captures = len(sess.captures)
        ring = sess.view()
        tree = sess.view("tree")
        hier = sess.view("hierarchical")
        assert tree.ops == sess.compiled_ops
        assert not np.allclose(tree.matrix, ring.matrix)
        assert hier.link_utilization() is not None
        assert len(sess.captures) == n_captures
        assert sess.view("tree") is tree            # memoized per binding

    def test_report_snapshot_and_render(self, phased_session):
        rep = phased_session.report()
        assert rep.phase_names() == ["fwd", "bwd", "optim"]
        txt = rep.render()
        assert "per-phase compiled collectives" in txt
        assert "optim" in txt

    def test_empty_phase_view_is_empty(self, phased_session):
        v = phased_session.view(phase="optim")
        assert v.matrix.sum() == 0.0 and v.summary == {}

    def test_host_transfer_list_reused_across_phases(self, mesh8):
        """Untagged transfers are copied per phase -- reusing one list must
        not mutate the caller's objects or double-count under one phase."""
        transfers = [HostTransfer("h2d", 0, 1024)]
        sess = MonitorSession(mesh=mesh8, name="ht")
        with sess.phase("a"):
            sess.add_host_transfers(transfers)
        with sess.phase("b"):
            sess.add_host_transfers(transfers)
        assert transfers[0].phase == ""            # caller object untouched
        assert sess.view(phase="a").matrix[0, 1] == 1024
        assert sess.view(phase="b").matrix[0, 1] == 1024
        assert sess.view().matrix[0, 1] == 2048
        # a pre-tagged transfer registers its phase for per-phase views
        sess.add_host_transfers([HostTransfer("d2h", 2, 64, phase="c")])
        assert "c" in sess.phase_names()
        assert sess.view(phase="c").matrix[3, 0] == 64

    def test_multi_capture_roofline_analyzes_per_module(self, phased_session):
        """Each capture's module is analyzed separately (concatenation
        would clobber same-named computations): the session roofline's
        totals equal the sum of per-capture analyses."""
        from repro.core import hlo_cost
        rep = phased_session.report()
        assert len(rep._hlo_texts) == len(phased_session.captures)
        per_module = [hlo_cost.analyze_hlo(t) for t in rep._hlo_texts]
        rl = roofline_of(rep, arch="phased", mesh_name="4x2")
        assert rl.flops_per_device == pytest.approx(
            sum(h.flops for h in per_module))
        assert rl.bytes_per_device == pytest.approx(
            sum(h.bytes_hbm for h in per_module))


@pytest.mark.compile
class TestCompatContract:
    """monitor_fn(...) must stay artifact-for-artifact equal to a
    single-phase MonitorSession over the same function."""

    @pytest.fixture(scope="class")
    def pair(self, mesh8):
        ws = NamedSharding(mesh8, P(None, "model"))
        xs = NamedSharding(mesh8, P("data", None))
        args = (jax.ShapeDtypeStruct((256, 256), jnp.float32),
                jax.ShapeDtypeStruct((128, 256), jnp.float32))

        def step(w, x):
            return ((x @ w) ** 2).mean()

        fn = jax.value_and_grad(step)
        old = monitor_fn(fn, *args, mesh=mesh8, name="toy",
                         in_shardings=(ws, xs),
                         host_transfers=[HostTransfer("h2d", 0, 128)])
        with MonitorSession(mesh=mesh8, name="toy") as sess:
            sess.capture(fn, *args, in_shardings=(ws, xs),
                         host_transfers=[HostTransfer("h2d", 0, 128)])
        return old, sess.report()

    def test_golden_equality(self, pair):
        old, new = pair
        np.testing.assert_allclose(old.matrix, new.matrix)
        assert old.compiled_summary == new.compiled_summary
        assert old.traced_summary == new.traced_summary
        assert set(old.per_primitive) == set(new.per_primitive)
        for k in old.per_primitive:
            np.testing.assert_allclose(old.per_primitive[k],
                                       new.per_primitive[k])
        np.testing.assert_allclose(old.link_matrix(), new.link_matrix())
        assert old.collective_seconds() == new.collective_seconds()
        assert old.collective_seconds_split() == \
            new.collective_seconds_split()
        assert old.total_wire_bytes() == new.total_wire_bytes()

    def test_monitor_fn_is_single_phase_session(self, pair):
        old, _ = pair
        assert old.phase_names() == ["main"]
        assert all(op.phase == "main" for op in old.compiled_ops)


@pytest.mark.compile
class TestSchemaRoundTrip:
    def test_phases_survive_save_load(self, phased_session, tmp_path):
        rep = phased_session.report()
        p = str(tmp_path / "v6.json")
        rep.save(p)
        d = json.loads(open(p).read())
        assert d["schema"] == "repro.comm_report.v9"
        assert [ph["name"] for ph in d["phases"]] == ["fwd", "bwd", "optim"]
        assert all("phase" in op for op in d["ops"])
        back = CommReport.load(p)
        assert back.phase_names() == rep.phase_names()
        for ph in rep.phase_names():
            np.testing.assert_allclose(back.view(phase=ph).matrix,
                                       rep.view(phase=ph).matrix)

    @pytest.mark.parametrize("old_schema", ["repro.comm_report.v1",
                                            "repro.comm_report.v2",
                                            "repro.comm_report.v3",
                                            "repro.comm_report.v4"])
    def test_older_schemas_still_load(self, phased_session, tmp_path,
                                      old_schema):
        rep = phased_session.report()
        p = str(tmp_path / "old.json")
        rep.save(p)
        d = json.loads(open(p).read())
        d["schema"] = old_schema
        d.pop("phases", None)
        for op in d["ops"]:
            op.pop("phase", None)
        for key in ("links", "link_matrix", "link_summary", "link_tiers",
                    "overlap", "hlo_gz"):
            d.pop(key, None)
        with open(p, "w") as f:
            json.dump(d, f)
        back = CommReport.load(p)
        assert back.phases == [] and back.phase_names() == []
        np.testing.assert_allclose(back.matrix, rep.matrix)
        assert back.collective_seconds() == rep.collective_seconds()

    def test_include_hlo_roofline_on_loaded(self, phased_session, tmp_path):
        rep = phased_session.report()
        p = str(tmp_path / "hlo.json")
        rep.save(p, include_hlo=True)
        d = json.loads(open(p).read())
        # one compressed module per capture (kept separate: computation
        # names are only unique within a module)
        assert len(d["hlo_gz"]) == len(phased_session.captures)
        back = CommReport.load(p)
        rl = roofline_of(back, arch="phased", mesh_name="4x2")
        live = roofline_of(rep, arch="phased", mesh_name="4x2")
        assert rl.compute_s > 0
        assert rl.flops_per_device == pytest.approx(live.flops_per_device)

    def test_hlo_not_persisted_by_default(self, phased_session, tmp_path):
        rep = phased_session.report()
        p = str(tmp_path / "nohlo.json")
        rep.save(p)
        assert "hlo_gz" not in json.loads(open(p).read())
        with pytest.raises(ValueError, match="include_hlo"):
            roofline_of(CommReport.load(p))


@pytest.mark.compile
class TestPhaseConsumers:
    def test_html_phase_tabs(self, phased_session, tmp_path):
        from repro.core import export
        p = str(tmp_path / "tabs.html")
        export.export_html(phased_session.report(), p)
        text = open(p).read()
        assert "class='tabs'" in text
        assert "all phases" in text
        for ph in ("fwd", "bwd", "optim"):
            assert f">{ph}</label>" in text
        assert "type='radio'" in text

    def test_perfetto_phase_lane(self, phased_session):
        from repro.core import export
        doc = export.chrome_trace(phased_session.report())
        events = doc["traceEvents"]
        lanes = [e for e in events if e.get("cat") == "phase"]
        lane_names = [e["name"] for e in lanes]
        # optim moves no bytes -> no span on the collective clock
        assert lane_names == ["fwd", "bwd"]
        meta = [e for e in events if e["ph"] == "M"
                and e["args"].get("name") == "phases"]
        assert meta, "phase lane thread metadata missing"
        ops = [e for e in events if e.get("cat") == "collective"]
        assert all("phase" in e["args"] for e in ops)
        json.dumps(doc)

    def test_sweep_table_by_phase(self, phased_session):
        from repro.sweep import SweepResult
        rep = phased_session.report()
        res = SweepResult(reports=[rep], failures=[], cache_hits=0,
                          compiles=1)
        table = res.summary_table(by_phase=True)
        assert "phase" in table.splitlines()[0]
        assert "fwd" in table and "bwd" in table and "optim" in table
        # one row per phase (+ header + separator)
        assert len(table.splitlines()) == 2 + 3
        both = res.summary_table(by_link=True, by_phase=True)
        assert "busiest link" in both and "overlap ms" in both
