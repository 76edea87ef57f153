"""Export subsystem: save/load round-trip, golden CSV/JSON, Perfetto schema,
HTML dashboard structure, report cache, and the terminal reporter helpers
(re-homed from test_comm_matrix so they run without hypothesis)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import CommReport, ReportCache, cache_key, export, monitor_fn
from repro.core.events import CollectiveOp, HostTransfer, Shape


@pytest.fixture(scope="module")
def report(mesh8):
    def step(w, x):
        return ((x @ w) ** 2).mean()

    return monitor_fn(
        jax.value_and_grad(step),
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        mesh=mesh8, name="toy",
        in_shardings=(NamedSharding(mesh8, P(None, "model")),
                      NamedSharding(mesh8, P("data", None))))


def hand_report() -> CommReport:
    """A fully hand-built report with known numbers (golden-file basis)."""
    op = CollectiveOp(kind="all-reduce", name="%ar.1",
                      result_shapes=[Shape("f32", (256,))],
                      replica_groups=[[0, 1, 2, 3]], op_name="psum")
    from repro.core import comm_matrix, hlo_parser
    mat = comm_matrix.matrix_for_ops([op], 4)
    return CommReport(
        name="golden", num_devices=4, traced=[], compiled_ops=[op],
        traced_summary={}, compiled_summary=hlo_parser.summarize([op]),
        matrix=mat,
        per_primitive=comm_matrix.per_primitive_matrices([op], 4),
        cost={"flops": 1.0}, memory_stats=None,
        trace_seconds=0.01, compile_seconds=0.02,
        host_transfers=[HostTransfer("h2d", 0, 64)])


class TestRoundTrip:
    pytestmark = pytest.mark.compile  # module fixture compiles

    def test_save_load_lossless(self, report, tmp_path):
        p = str(tmp_path / "r.json")
        report.save(p)
        back = CommReport.load(p)
        assert back.name == report.name
        assert back.num_devices == report.num_devices
        assert back.algorithm == report.algorithm
        np.testing.assert_allclose(back.matrix, report.matrix)
        assert set(back.per_primitive) == set(report.per_primitive)
        for k in back.per_primitive:
            np.testing.assert_allclose(back.per_primitive[k],
                                       report.per_primitive[k])
        assert back.compiled_summary == json.loads(
            json.dumps(report.compiled_summary))
        assert len(back.compiled_ops) == len(report.compiled_ops)
        for a, b in zip(back.compiled_ops, report.compiled_ops):
            assert (a.kind, a.payload_bytes, a.group_size, a.weight) == \
                (b.kind, b.payload_bytes, b.group_size, b.weight)
        assert len(back.traced) == len(report.traced)
        assert back.topo.axis_names == report.topo.axis_names
        # a loaded report renders and re-exports like a fresh one
        assert "comm matrix" in back.render()

    def test_legacy_keys_preserved(self, report, tmp_path):
        """save() output stays a superset of the old dump_report layout."""
        p = str(tmp_path / "r.json")
        report.save(p)
        d = json.loads(open(p).read())
        assert {"name", "summary", "ops", "matrix",
                "traced_summary", "num_devices"} <= set(d)
        assert d["schema"] == export.serialize.SCHEMA
        assert len(d["matrix"]) == report.num_devices + 1
        # old-style op entries keep their repr'd shapes
        assert all("shapes" in op for op in d["ops"])

    def test_view_rebinding_no_recompile(self, report):
        """Algorithm comparison is a lazy view binding; ``rebound`` (the
        sweep derive path) snapshots it into a sibling report."""
        tv = report.view("tree")
        assert not np.allclose(tv.matrix, report.matrix)
        tree = report.rebound("tree")
        assert tree.algorithm == "tree"
        assert tree.compiled_ops is report.compiled_ops or \
            len(tree.compiled_ops) == len(report.compiled_ops)
        np.testing.assert_allclose(tree.matrix, tv.matrix)
        # same payloads, different wire model
        assert sum(r["payload_bytes"]
                   for r in tree.compiled_summary.values()) == \
            sum(r["payload_bytes"] for r in report.compiled_summary.values())
        # the deprecated eager spelling is gone
        assert not hasattr(report, "with_algorithm")


class TestSchemaSections:
    """Physical-link + overlap sections (since schema v3), the v4 phase
    section, and v1/v2/v3 backward-compat loads."""

    pytestmark = pytest.mark.compile  # module fixture compiles

    def test_v6_writes_link_sections(self, report, tmp_path):
        p = str(tmp_path / "v6.json")
        report.save(p)
        d = json.loads(open(p).read())
        assert d["schema"] == "repro.comm_report.v9"
        assert len(d["link_matrix"]) == report.num_devices + 1
        assert d["links"], "per-link rows missing"
        for row in d["links"]:
            assert {"kind", "src", "dst", "axis", "bytes", "bandwidth",
                    "seconds"} <= set(row)
            assert row["kind"] in ("ici", "dcn")
        assert "ici" in d["link_summary"]

    def test_v6_writes_phase_section(self, report, tmp_path):
        """monitor_fn is a single-phase session: its snapshot carries one
        'main' phase record and phase tags on every op."""
        p = str(tmp_path / "v6.json")
        report.save(p)
        d = json.loads(open(p).read())
        assert [ph["name"] for ph in d["phases"]] == ["main"]
        assert d["phases"][0]["num_captures"] == 1
        assert all(op["phase"] == "main" for op in d["ops"])

    def test_v6_writes_overlap_sections(self, report, tmp_path):
        p = str(tmp_path / "v6.json")
        report.save(p)
        d = json.loads(open(p).read())
        assert "ici" in d["link_tiers"]
        assert {"bytes", "busy_seconds"} <= set(d["link_tiers"]["ici"])
        ov = d["overlap"]
        assert {"collective_ici_s", "collective_dcn_s",
                "collective_overlap_s", "collective_serial_s"} <= set(ov)
        assert ov["collective_overlap_s"] <= \
            ov["collective_serial_s"] + 1e-15
        assert ov["collective_serial_s"] == pytest.approx(
            ov["collective_ici_s"] + ov["collective_dcn_s"])

    @pytest.mark.parametrize("old_schema", ["repro.comm_report.v1",
                                            "repro.comm_report.v2",
                                            "repro.comm_report.v3",
                                            "repro.comm_report.v4",
                                            "repro.comm_report.v5",
                                            "repro.comm_report.v6",
                                            "repro.comm_report.v7"])
    def test_old_file_loads_and_rederives_links(self, report, tmp_path,
                                                old_schema):
        """Files written by previous schemas (no link/overlap/phase/
        schedule sections) load fine; the derived views recompute from
        ops+topo."""
        p = str(tmp_path / "old.json")
        report.save(p)
        d = json.loads(open(p).read())
        for key in ("links", "link_matrix", "link_summary", "link_tiers",
                    "overlap", "phases", "hlo_gz", "schedules"):
            d.pop(key, None)
        for op in d["ops"]:
            op.pop("phase", None)
        d["schema"] = old_schema
        with open(p, "w") as f:
            json.dump(d, f)
        back = CommReport.load(p)
        lu = back.link_utilization()
        assert lu is not None and lu.total_bytes() > 0
        np.testing.assert_allclose(back.link_matrix(), report.link_matrix())
        assert back.collective_seconds_split() == \
            report.collective_seconds_split()

    def test_unknown_schema_rejected(self, report, tmp_path):
        p = str(tmp_path / "bad.json")
        report.save(p)
        d = json.loads(open(p).read())
        d["schema"] = "repro.comm_report.v99"
        with open(p, "w") as f:
            json.dump(d, f)
        with pytest.raises(ValueError):
            CommReport.load(p)

    def test_topoless_report_has_no_link_view(self, tmp_path):
        rep = hand_report()          # built without a topology
        p = str(tmp_path / "t.json")
        rep.save(p)
        d = json.loads(open(p).read())
        assert "links" not in d
        assert CommReport.load(p).link_utilization() is None

    def test_html_link_panel(self, report, tmp_path):
        p = str(tmp_path / "links.html")
        export.export_html(report, p)
        text = open(p).read()
        assert "physical links" in text
        assert "link kind" in text


def sparse_hand_report() -> CommReport:
    """The hand-built golden report in sparse (COO) form, with a topology
    so the link section is exercised too."""
    from repro.core import comm_matrix, hlo_parser
    from repro.core.topology import MeshTopology
    op = CollectiveOp(kind="all-reduce", name="%ar.1",
                      result_shapes=[Shape("f32", (256,))],
                      replica_groups=[[0, 1, 2, 3]], op_name="psum")
    return CommReport(
        name="golden_sparse", num_devices=4, traced=[], compiled_ops=[op],
        traced_summary={}, compiled_summary=hlo_parser.summarize([op]),
        matrix=comm_matrix.add_host_transfers(
            comm_matrix.matrix_for_ops([op], 4, sparse=True),
            [HostTransfer("h2d", 0, 64)]),
        per_primitive=comm_matrix.per_primitive_matrices([op], 4,
                                                         sparse=True),
        cost={"flops": 1.0}, memory_stats=None,
        trace_seconds=0.01, compile_seconds=0.02,
        topo=MeshTopology(axis_names=("data",), axis_sizes=(4,)),
        host_transfers=[HostTransfer("h2d", 0, 64)])


class TestSparseSerialization:
    """Schema v6: sparse matrices round-trip as COO dicts, never dense."""

    def test_sparse_round_trip(self, tmp_path):
        from repro.core.sparse import is_sparse
        rep = sparse_hand_report()
        p = str(tmp_path / "s.json")
        rep.save(p)
        back = CommReport.load(p)
        assert is_sparse(back.matrix)
        np.testing.assert_array_equal(back.matrix.to_dense(),
                                      rep.matrix.to_dense())
        assert set(back.per_primitive) == set(rep.per_primitive)
        for k in back.per_primitive:
            assert is_sparse(back.per_primitive[k])
            np.testing.assert_array_equal(
                back.per_primitive[k].to_dense(),
                rep.per_primitive[k].to_dense())
        assert back.compiled_summary == json.loads(
            json.dumps(rep.compiled_summary))

    def test_sparse_file_layout(self, tmp_path):
        """The on-disk form is the COO dict -- O(nnz), not a nested list --
        and the derived link section drops its dense matrix."""
        rep = sparse_hand_report()
        p = str(tmp_path / "s.json")
        rep.save(p)
        d = json.loads(open(p).read())
        assert d["schema"] == "repro.comm_report.v9"
        assert d["matrix"]["format"] == "coo"
        assert len(d["matrix"]["src"]) == rep.matrix.nnz
        assert all(m["format"] == "coo"
                   for m in d["per_primitive"].values())
        assert "link_matrix" not in d
        assert d["links"] and all(r["bytes"] > 0 for r in d["links"])

    def test_dense_report_stays_dense(self, tmp_path):
        """A dense report's file keeps the v1...v5 nested-list spelling."""
        rep = hand_report()
        p = str(tmp_path / "d.json")
        rep.save(p)
        d = json.loads(open(p).read())
        assert isinstance(d["matrix"], list)
        back = CommReport.load(p)
        assert isinstance(back.matrix, np.ndarray)

    def test_unknown_matrix_format_rejected(self, tmp_path):
        rep = sparse_hand_report()
        p = str(tmp_path / "s.json")
        rep.save(p)
        d = json.loads(open(p).read())
        d["matrix"]["format"] = "csr"
        with open(p, "w") as f:
            json.dump(d, f)
        with pytest.raises(ValueError, match="unknown matrix format"):
            CommReport.load(p)

    def test_loaded_sparse_view_stays_sparse(self, tmp_path):
        """CommReport.view on a loaded sparse snapshot keeps derived
        bindings sparse (no dense rebuild on algorithm rebind)."""
        from repro.core.sparse import is_sparse
        rep = sparse_hand_report()
        p = str(tmp_path / "s.json")
        rep.save(p)
        back = CommReport.load(p)
        assert back.view().use_sparse
        assert is_sparse(back.view("tree").matrix)

    def test_sparse_matrix_csv_long_form(self, tmp_path):
        rep = sparse_hand_report()
        p = str(tmp_path / "m.csv")
        export.export_matrix_csv(rep, p)
        lines = open(p).read().strip().splitlines()
        assert lines[0] == "src,dst,bytes"
        assert len(lines) == 1 + rep.matrix.nnz
        assert any(line.startswith("host,gpu0,") for line in lines)

    def test_sparse_html_renders(self, tmp_path):
        rep = sparse_hand_report()
        p = str(tmp_path / "s.html")
        export.export_html(rep, p)
        text = open(p).read()
        assert "golden_sparse" in text and "physical links" in text

    def test_sparse_heatmap_renders(self):
        out = sparse_hand_report().heatmap()
        assert "max cell" in out


class TestGolden:
    """Exact expected artifacts for a hand-built 4-device all-reduce."""

    def test_golden_csv(self, tmp_path):
        p = str(tmp_path / "g.csv")
        export.export_summary_csv(hand_report(), p)
        # ring all-reduce of S=1024B over 4 ranks: 2*(4-1)/4*1024 = 1536 B
        # per rank -> 6144 B on the wire
        assert open(p).read() == (
            "config,mesh,algorithm,num_devices,primitive,calls,"
            "payload_bytes,wire_bytes\n"
            "golden,4dev,ring,4,all-reduce,1,1024,6144.0\n")

    def test_golden_matrix_csv(self, tmp_path):
        p = str(tmp_path / "m.csv")
        export.export_matrix_csv(hand_report(), p)
        lines = open(p).read().splitlines()
        assert lines[0] == ",host,gpu0,gpu1,gpu2,gpu3"
        # bidirectional ring: edge 0->1 carries half the 1536 B per-rank
        # wire bytes, the other half flows 0->3 (col order: name, host,
        # gpu0..gpu3 -> gpu1 is index 3, gpu3 is index 5)
        assert lines[1] == "host,0,0,0,0,0"
        assert lines[2].split(",")[3] == "768"
        assert lines[2].split(",")[5] == "768"

    def test_sweep_document_loads_as_list(self, tmp_path):
        p = str(tmp_path / "sweep.json")
        export.export_comparison_json([hand_report(), hand_report()], p)
        reports = export.load_json_reports(p)
        assert len(reports) == 2 and reports[0].name == "golden"
        with pytest.raises(ValueError):
            export.load_json(p)   # single-report loader refuses multi-docs

    def test_golden_json_roundtrip(self, tmp_path):
        p = str(tmp_path / "g.json")
        rep = hand_report()
        rep.save(p)
        back = CommReport.load(p)
        assert back.compiled_summary["all-reduce"]["calls"] == 1
        assert back.matrix.sum() == rep.matrix.sum() == pytest.approx(6144)
        assert back.host_transfers[0].nbytes == 64


class TestPerfetto:
    pytestmark = pytest.mark.compile  # module fixture compiles

    def test_chrome_trace_schema(self, report):
        doc = export.chrome_trace([report, report.rebound("tree")])
        assert set(doc) >= {"traceEvents", "displayTimeUnit"}
        events = doc["traceEvents"]
        assert events, "no events emitted"
        for e in events:
            assert {"name", "ph", "pid", "tid"} <= set(e)
            assert e["ph"] in ("X", "M")
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] > 0
                assert e["cat"] in ("collective", "tier", "phase")
                if e["cat"] == "collective":
                    assert e["args"]["payload_bytes"] >= 0
            else:
                assert "name" in e["args"]
        # each track's spans are laid out in non-decreasing start order
        # (tracks themselves may overlap: that is the per-tier pipelining)
        for pid in {e["pid"] for e in events}:
            for tid in {e["tid"] for e in events if e["pid"] == pid}:
                ts = [e["ts"] for e in events
                      if e["pid"] == pid and e["tid"] == tid
                      and e["ph"] == "X"]
                assert ts == sorted(ts)
        json.dumps(doc)  # must be JSON-serializable as-is

    def test_tier_lanes_from_schedules(self, report):
        """Overlap-aware per-tier lanes: phase spans render straight from
        the decomposition schedules on dedicated ICI / DCN tracks."""
        events = export.trace_events(report)
        lane_meta = {e["args"]["name"]: e["tid"] for e in events
                     if e["ph"] == "M" and e["tid"] > 0}
        assert "ici lane" in lane_meta and "dcn lane" in lane_meta
        tiers = [e for e in events if e.get("cat") == "tier"]
        assert tiers, "no tier-lane spans emitted"
        for e in tiers:
            assert e["args"]["tier"] in ("ici", "dcn")
            assert e["args"]["structure"] in ("ring", "tree", "a2a",
                                              "pairs")
            assert e["args"]["bytes_per_rank"] >= 0
        # mesh8 is single-pod: every phase must ride the ICI lane
        assert {e["tid"] for e in tiers} == {lane_meta["ici lane"]}
        # an op's span covers its phases
        ops = [e for e in events if e.get("cat") == "collective"]
        assert ops and all(e["dur"] > 0 for e in ops)

    def test_one_process_per_report(self, report):
        doc = export.chrome_trace([report, report])
        assert len({e["pid"] for e in doc["traceEvents"]}) == 2


class TestHtml:
    pytestmark = pytest.mark.compile  # module fixture compiles

    def test_dashboard_structure(self, report, tmp_path):
        p = str(tmp_path / "d.html")
        export.export_html([report, report.rebound("tree")], p)
        html_text = open(p).read()
        assert html_text.count("<h2>") == 2
        assert "td class='q" in html_text          # ramp-bucketed cells
        assert "prefers-color-scheme: dark" in html_text
        assert "raw values" in html_text           # table view fallback
        assert "legend" in html_text

    def test_large_matrix_coarsens(self):
        rep = hand_report()
        rep.matrix = np.ones((257, 257))
        rep.per_primitive = {}
        html_text = export.render_dashboard(rep)
        assert "device blocks of" in html_text


class TestCache:
    def test_key_sensitivity(self):
        base = cache_key("a/v1", "4x2:data,model", "ring", jax_version="1")
        assert cache_key("a/v1", "4x2:data,model", "ring",
                         jax_version="1") == base
        assert cache_key("a/v2", "4x2:data,model", "ring",
                         jax_version="1") != base
        assert cache_key("a/v1", "8:data", "ring", jax_version="1") != base
        assert cache_key("a/v1", "4x2:data,model", "tree",
                         jax_version="1") != base
        assert cache_key("a/v1", "4x2:data,model", "ring",
                         jax_version="2") != base

    def test_key_separates_cpu_from_tpu(self):
        """A report compiled on forced host devices is never a chip's."""
        from repro.core.report_cache import device_id
        assert device_id() == "cpu:cpu"
        cpu = cache_key("a/v1", "1:data", "ring", jax_version="1")
        assert cpu == cache_key("a/v1", "1:data", "ring", jax_version="1",
                                device="cpu:cpu")
        assert cpu != cache_key("a/v1", "1:data", "ring", jax_version="1",
                                device="tpu:TPU v5 lite")

    def test_put_get_roundtrip(self, tmp_path):
        cache = ReportCache(root=str(tmp_path / "cache"))
        key = cache_key("golden/v1", "4:data", "ring")
        assert cache.get(key) is None
        cache.put(key, hand_report(), meta={"config": "golden"})
        back = cache.get(key)
        assert back is not None and back.name == "golden"
        assert back.meta["config"] == "golden"
        assert cache.hits == 1 and cache.misses == 1
        assert len(cache.entries()) == 1
        assert cache.clear() == 1 and cache.entries() == []

    def test_corrupt_entry_is_miss(self, tmp_path):
        cache = ReportCache(root=str(tmp_path / "cache"))
        key = cache_key("golden/v1", "4:data", "ring")
        cache.put(key, hand_report())
        with open(cache.path_for(key), "w") as f:
            f.write("{not json")
        assert cache.get(key) is None

    def test_phase_is_key_neutral(self):
        """Satellite: a sweep cell keyed with phase= addresses the SAME
        entry as the whole session -- phases are views, not compiles."""
        base = cache_key("a/v1", "4x2:data,model", "ring", jax_version="1")
        assert cache_key("a/v1", "4x2:data,model", "ring", jax_version="1",
                         phase="decode") == base
        assert cache_key("a/v1", "4x2:data,model", "ring", jax_version="1",
                         phase="prefill") == base

    def test_phase_aware_get_reuses_session_snapshot(self, tmp_path):
        """A phase-keyed lookup hands back the cached whole-session
        snapshot (per-phase artifacts derive lazily); a phase the snapshot
        never captured is a miss."""
        from repro.core.events import PhaseRecord
        rep = hand_report()
        rep.phases = [PhaseRecord(name="prefill", num_captures=1),
                      PhaseRecord(name="decode", num_captures=1)]
        rep.compiled_ops[0].phase = "decode"
        cache = ReportCache(root=str(tmp_path / "cache"))
        key = cache_key("serve/v1", "4:data", "ring",
                        phase="decode")      # == the session's key
        cache.put(key, rep)
        back = cache.get(key, phase="decode")
        assert back is not None
        assert back.phase_names() == ["prefill", "decode"]
        # the decode view derives from the snapshot, nothing recaptured
        assert back.view(phase="decode").summary != {}
        assert back.view(phase="prefill").summary == {}
        # a phase the session never captured must miss
        assert cache.get(key, phase="bwd") is None


class TestReporter:
    """Terminal-reporter coverage (moved from test_comm_matrix, which now
    skips entirely when hypothesis is absent)."""

    def test_heatmap_renders(self):
        from repro.core import reporter
        mat = np.random.default_rng(0).random((9, 9)) * 1e9
        txt = reporter.ascii_heatmap(mat, title="test")
        assert "test" in txt and len(txt.splitlines()) >= 10

    def test_heatmap_coarsens_large(self):
        from repro.core import reporter
        mat = np.ones((257, 257))
        txt = reporter.ascii_heatmap(mat, max_devices=32)
        assert "blocks of" in txt

    def test_coarsen_preserves_total(self):
        from repro.core import reporter
        mat = np.random.default_rng(1).random((101, 101))
        small, block = reporter.coarsen_matrix(mat, max_devices=16)
        assert block > 1 and small.shape[0] <= 17 + 1
        assert small.sum() == pytest.approx(mat.sum())

    def test_csv(self):
        from repro.core import reporter
        mat = np.arange(9).reshape(3, 3).astype(float)
        csv = reporter.matrix_to_csv(mat)
        assert csv.splitlines()[0] == ",host,gpu0,gpu1"
        assert csv.splitlines()[1] == "host,0,1,2"

    def test_human_bytes(self):
        from repro.core.reporter import human_bytes
        assert human_bytes(0) == "0 B"
        assert human_bytes(1024) == "1.00 KiB"
        assert human_bytes(3.5 * 2**30) == "3.50 GiB"
