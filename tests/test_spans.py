"""Spans and counters (repro.core.spans): the log's records, the JAX
compile-path events under them, the ring's bound, the profiler's clock,
and the spans that capture, report(), the exports and generate emit."""
import glob
import json

import jax
import jax.numpy as jnp
import pytest

from repro.core import spans


def records(group):
    return [r for r in spans.snapshot()["records"] if r["group"] == group]


def by_name(recs, name):
    return [r for r in recs if r["name"] == name]


class TestSpans:
    def test_nesting_parent_and_group(self):
        g = spans.new_group()
        with spans.span("t.outer", g) as outer:
            with spans.span("t.inner") as inner:
                pass
        assert outer.parent is None
        assert inner.parent == outer.id and inner.group == g
        recs = records(g)
        assert [r["name"] for r in recs] == ["t.inner", "t.outer"]
        o, i = by_name(recs, "t.outer")[0], by_name(recs, "t.inner")[0]
        assert i["parent"] == o["id"]
        # the child lies inside its parent: the parent's self time is its
        # length less the child's, and not negative
        assert o["start_ns"] <= i["start_ns"] <= i["end_ns"] <= o["end_ns"]
        self_ns = (o["end_ns"] - o["start_ns"]) - (i["end_ns"] - i["start_ns"])
        assert 0 <= self_ns <= o["end_ns"] - o["start_ns"]
        assert outer.seconds == (o["end_ns"] - o["start_ns"]) / 1e9

    def test_groups_grow_and_a_span_without_one_stands_alone(self):
        a, b = spans.new_group(), spans.new_group()
        assert b > a
        with spans.span("t.lone") as s:
            assert s.id > b
        assert s.group is None and s.parent is None
        # an explicit group wins over the parent's
        with spans.span("t.p", a):
            with spans.span("t.c", b) as c:
                pass
        assert c.group == b

    def test_a_group_block_reaches_top_spans_only(self):
        a, b = spans.new_group(), spans.new_group()
        with spans.group(a):
            with spans.span("t.top") as top:
                with spans.span("t.kid", b) as kid:
                    with spans.span("t.grandkid") as grandkid:
                        pass
            with spans.group(b):
                with spans.span("t.inner_block") as inner:
                    pass
            with spans.span("t.after") as after:
                pass
        with spans.span("t.outside") as outside:
            pass
        assert top.group == a and after.group == a
        # an explicit group wins, and children follow their parent
        assert kid.group == b and grandkid.group == b
        assert inner.group == b
        assert outside.group is None

    def test_counters_land_on_the_innermost_span(self):
        g = spans.new_group()
        with spans.span("t.outer", g):
            spans.count("t.n", 2)
            with spans.span("t.inner"):
                spans.count("t.n")
                spans.count("t.n", 3)
        recs = records(g)
        assert by_name(recs, "t.inner")[0]["counts"] == {"t.n": 4}
        assert by_name(recs, "t.outer")[0]["counts"] == {"t.n": 2}
        spans.count("t.n", 1)          # no span open: nothing, no error

    def test_jax_duration_events_become_children(self):
        g = spans.new_group()
        with spans.span("t.compile", g) as s:
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", 0.002)
            jax.monitoring.record_event_duration_secs("/t/not_ours", 1.0)
        recs = records(g)
        [ev] = by_name(recs, "/jax/core/compile/backend_compile_duration")
        assert ev["parent"] == s.id and ev["group"] == g
        assert ev["end_ns"] - ev["start_ns"] == 2_000_000
        assert s.start_ns <= ev["end_ns"] <= s.end_ns
        assert not by_name(recs, "/t/not_ours")

    def test_a_real_trace_lands_under_the_open_span(self):
        g = spans.new_group()
        with spans.span("t.jit", g) as s:
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        names = {r["name"] for r in records(g) if r["parent"] == s.id}
        assert "/jax/core/compile/jaxpr_trace_duration" in names
        assert "/jax/core/compile/jaxpr_to_mlir_module_duration" in names

    def test_events_with_no_span_open_are_not_recorded(self):
        def total():
            snap = spans.snapshot()
            return len(snap["records"]) + snap["dropped"]
        before = total()
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", 0.001)
        assert total() == before

    def test_ring_drops_the_oldest_and_counts(self, monkeypatch):
        monkeypatch.setattr(spans, "_LOG", spans._Log(4))
        g = spans.new_group()
        for i in range(6):
            with spans.span(f"t.{i}", g):
                pass
        snap = spans.snapshot()
        assert snap["capacity"] == 4 and snap["dropped"] == 2
        assert [r["name"] for r in snap["records"]] == [
            "t.2", "t.3", "t.4", "t.5"]

    def test_records_with_the_profiler_off_and_dumps(self, tmp_path):
        g = spans.new_group()
        with spans.span("t.off", g):
            pass
        assert by_name(records(g), "t.off")
        path = spans.dump(str(tmp_path / "spans.json"))
        with open(path) as f:
            dumped = json.load(f)
        assert any(r["name"] == "t.off" and r["group"] == g
                   for r in dumped["records"])
        assert set(dumped) == {"capacity", "dropped", "records"}

    def test_the_profilers_clock(self, tmp_path):
        """A span starts within 1 ms of its TraceAnnotation event in the
        profiler's host plane, whose times count from the trace's start."""
        jax.profiler.start_trace(str(tmp_path))
        try:
            with spans.span("t.clock_probe") as s:
                jnp.ones(8).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                           recursive=True)
        profile = jax.profiler.ProfileData.from_file(path)
        planes = list(profile.planes)     # views into ``profile``
        start = [v for p in planes if p.name == "Task Environment"
                 for k, v in list(p.stats) if k == "profile_start_time"]
        events = [e for p in planes if p.name == "/host:CPU"
                  for line in p.lines for e in line.events
                  if e.name == "t.clock_probe"]
        assert len(start) == 1 and len(events) == 1
        assert abs(start[0] + events[0].start_ns - s.start_ns) < 1e6
        assert abs(events[0].duration_ns - (s.end_ns - s.start_ns)) < 1e6


@pytest.mark.compile
class TestCallSites:
    """capture, report(), save, export_report and generate on a tiny
    model emit the spans and counters the benchmark reads."""

    @pytest.fixture(scope="class")
    def served(self, tmp_path_factory):
        from repro.core import export
        from repro.launch import serve
        args = serve.parse_args(["--arch", "granite_3_2b", "--mesh", "2x2",
                                 "--batch", "2", "--prompt-len", "8",
                                 "--tokens", "3"])
        mesh, shd, model, params = serve.build(args)
        prompts = serve.prompts_for(args, model.cfg.vocab_size)
        steps = serve.serve_steps(model, shd, args)
        first = spans.new_group()
        serve.serve(model, params, shd, prompts, args.tokens, steps)
        sess = serve.monitor(model, steps, mesh, args.batch, args.prompt_len,
                             name="serve")
        rep = sess.report()
        out = tmp_path_factory.mktemp("report")
        rep.save(str(out / "report.json"))
        export.export_report(rep, "html", str(out / "report.html"))
        # the batch after the capture: the job traces its steps again
        again = spans.new_group()
        serve.serve(model, params, shd, prompts, args.tokens, steps)
        snap = spans.snapshot()["records"]
        batches = sorted({r["group"] for r in snap
                          if r["name"] == "serve.prefill"
                          and r["group"] > first})
        return {"sess": sess, "report": rep,
                "session": records(sess.span_group),
                "batches": [records(b) for b in batches],
                "after": [b for b in batches if b > again]}

    def test_capture_spans_and_counters(self, served):
        sess, recs = served["sess"], served["session"]
        for name in ("capture.lower", "capture.compile", "capture.hlo_text",
                     "capture.analyze_hlo", "capture.analyses"):
            assert len(by_name(recs, name)) == 2, name
        for cap, lower, comp in zip(sess.captures,
                                    by_name(recs, "capture.lower"),
                                    by_name(recs, "capture.compile")):
            # the capture's timings are its spans
            assert cap.trace_seconds == (
                lower["end_ns"] - lower["start_ns"]) / 1e9
            assert cap.compile_seconds == (
                comp["end_ns"] - comp["start_ns"]) / 1e9
        # no counter on the captures; each view counts the distinct shapes
        # it decomposed and the edges it placed
        from repro.core.comm_matrix import schedule_edge_arrays
        batch = sess.view().schedule_batch()
        edges = sum(schedule_edge_arrays(s)[0].size for s in batch.schedules)
        assert batch.num_distinct > 0 and edges > 0
        for r in recs:
            if r["name"] == "view.schedule":
                assert set(r["counts"]) == {"view.shapes"}
            elif r["name"] in ("view.matrix", "view.per_primitive"):
                assert set(r["counts"]) == {"view.edges"}
            else:
                assert r["counts"] is None, r["name"]
        # report()'s own views, the first to close: the whole session's
        matrix = by_name(recs, "view.matrix")[0]
        sched = [r for r in recs if r["parent"] == matrix["id"]]
        assert sched[0]["counts"] == {"view.shapes": batch.num_distinct}
        assert matrix["counts"] == {"view.edges": edges}
        assert by_name(recs, "view.per_primitive")[0]["counts"] == {
            "view.edges": edges}
        lowers = {r["id"] for r in by_name(recs, "capture.lower")}
        under = {r["name"] for r in recs if r["parent"] in lowers}
        assert "/jax/core/compile/jaxpr_trace_duration" in under
        assert "/jax/core/compile/jaxpr_to_mlir_module_duration" in under

    def test_report_and_export_spans(self, served):
        recs = served["session"]
        top = {r["name"] for r in recs if r["parent"] is None}
        assert {"view.matrix", "view.per_primitive", "export.json",
                "export.html"} <= top
        matrix = by_name(recs, "view.matrix")[0]
        sched = [r for r in by_name(recs, "view.schedule")
                 if r["parent"] == matrix["id"]]
        assert len(sched) == 1
        # the report carries its session's group to its exports
        assert served["report"].span_group == served["sess"].span_group

    def test_generate_spans(self, served):
        for recs in served["batches"]:
            assert len(by_name(recs, "serve.prefill")) == 1
            decode = by_name(recs, "serve.decode")
            assert len(decode) == 2 == len(by_name(recs, "serve.sample"))
            # every decode step consumed the cache it was given
            assert all(r["counts"] == {"serve.decode_steps": 1,
                                       "serve.cache_donated": 1}
                       for r in decode)

    def test_the_capture_forces_a_retrace(self, served):
        [after] = served["after"]
        serve_ids = {r["id"] for r in records(after)
                     if r["name"].startswith("serve.")}
        under = {r["name"] for r in records(after)
                 if r["parent"] in serve_ids}
        assert "/jax/core/compile/jaxpr_trace_duration" in under
