"""The readers of the program's span log (bench/metrics/ over
bench/spanlog.py), on a synthetic log: a warm-up cycle and a window of two,
each a served batch and a report."""
import sys
import types

import pytest

import harness
from repro.core import spans

JAXPR = "/jax/core/compile/jaxpr_trace_duration"
MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/cache_retrieval_time_sec"
MS = 1_000_000


def metric(name):
    return harness.load_module(f"{harness.BENCH}/metrics/{name}.py",
                               "test_metric_" + name.replace(".", "_"))


class Log:
    """Writes records straight into a fresh ring, in the order they
    close, at times given in milliseconds."""

    def __init__(self, monkeypatch, capacity=1 << 12):
        self.log = spans._Log(capacity)
        monkeypatch.setattr(spans, "_LOG", self.log)

    def add(self, name, start, end, group, parent=None, counts=None):
        rid = next(spans._ids)
        self.log.append((rid, name, start * MS, end * MS, parent, group,
                         counts))
        return rid

    def report(self, t):
        """One report from ``t`` ms: jaxpr traces 0-10 (one nested) and
        to-MLIR 10-14 under capture.lower; hlo text 1, analyze 2,
        analyses 3; decompose 1 inside a matrix of 4, per-primitive 2;
        exports 5 and 7, the HTML one decomposing again."""
        g = spans.new_group()
        lower = next(spans._ids)
        for s, e, name in ((t + 2, t + 5, JAXPR), (t, t + 10, JAXPR),
                           (t + 10, t + 14, MLIR)):
            self.add(name, s, e, g, parent=lower)
        self.log.append((lower, "capture.lower", (t) * MS, (t + 15) * MS,
                         None, g, None))
        self.add("capture.compile", t + 15, t + 20, g)
        self.add("capture.hlo_text", t + 20, t + 21, g)
        self.add("capture.analyze_hlo", t + 21, t + 23, g)
        self.add("capture.analyses", t + 23, t + 26, g)
        matrix = next(spans._ids)
        self.add("view.schedule", t + 26, t + 27, g, parent=matrix)
        self.log.append((matrix, "view.matrix", (t + 26) * MS, (t + 30) * MS,
                         None, g, None))
        self.add("view.per_primitive", t + 30, t + 32, g)
        self.add("export.json", t + 32, t + 37, g)
        html = next(spans._ids)
        self.add("view.schedule", t + 37, t + 39, g, parent=html)
        self.log.append((html, "export.html", (t + 37) * MS, (t + 44) * MS,
                         None, g, None))

    def batch(self, t, retrace):
        """One batch from ``t`` ms: prefill 10, then two decode steps of 3
        and samples of 1; with ``retrace`` a 6 ms compile holding a 2 ms
        cache read under prefill and a 1 ms trace under the first decode."""
        g = spans.new_group()
        pre = next(spans._ids)
        if retrace:
            self.add(CACHE, t + 2, t + 4, g, parent=pre)
            self.add(COMPILE, t + 1, t + 7, g, parent=pre)
        self.log.append((pre, "serve.prefill", t * MS, (t + 10) * MS, None,
                         g, None))
        for i in range(2):
            s = t + 10 + 4 * i
            dec = next(spans._ids)
            if retrace and i == 0:
                self.add(JAXPR, s, s + 1, g, parent=dec)
            self.log.append((dec, "serve.decode", s * MS, (s + 3) * MS, None,
                             g, {"serve.decode_steps": 1}))
            self.add("serve.sample", s + 3, s + 4, g)

    def window(self):
        self.batch(0, retrace=False)          # warm-up: compiled in set-up
        self.report(100)
        self.batch(200, retrace=True)
        self.report(300)
        self.batch(400, retrace=True)
        self.report(500)


def run_of(reports=2, cycles=2, requests=4, batch=2):
    cell = types.SimpleNamespace(traffic={"batch": batch})
    return harness.Run(cell=cell, peaks=None, reports=reports, cycles=cycles,
                       counts={"requests": requests})


@pytest.fixture
def log(monkeypatch):
    log = Log(monkeypatch)
    log.window()
    return log


@pytest.mark.parametrize("name,want", [
    ("capture.jaxpr_s", 0.010),       # 0-10 with 2-5 inside: once
    ("capture.mlir_s", 0.004),
    ("capture.hlo_text_s", 0.001),
    ("capture.analyze_s", 0.002),
    ("capture.analyses_s", 0.003),
    ("report.schedule_s", 0.001),     # the export's decomposition left out
    ("report.place_s", 0.005),        # matrix 4 less its decompose 1, + 2
    ("report.export_json_s", 0.005),
    ("report.export_html_s", 0.007),
    # per cycle: a 6 ms compile holding its 2 ms cache read, and 1 ms trace
    ("serve.retrace_s", 0.007),
    # per step: (3 + 1) ms, less the first step's 1 ms trace every batch
    ("serve.dispatch_ms", 3.5),
])
def test_reader_on_a_synthetic_window(log, name, want):
    assert metric(name).read(run_of()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", [
    "capture.jaxpr_s", "capture.mlir_s", "capture.hlo_text_s",
    "capture.analyze_s", "capture.analyses_s", "report.schedule_s",
    "report.place_s", "report.export_json_s", "report.export_html_s",
    "serve.retrace_s", "serve.dispatch_ms"])
class TestNothingToRead:
    def test_no_window(self, log, name):
        assert metric(name).read(run_of(reports=0, cycles=0,
                                        requests=0)) is None

    def test_fewer_groups_than_the_window(self, log, name):
        # three groups of each kind: a window of three leaves no warm-up
        assert metric(name).read(run_of(reports=3, cycles=3,
                                        requests=6)) is None

    def test_dropped_records(self, monkeypatch, name):
        # the ring dropped the warm-up and the window's first records:
        # nothing is read
        log = Log(monkeypatch, capacity=30)
        log.window()
        assert log.log.dropped
        assert metric(name).read(run_of()) is None

    def test_a_program_without_the_log(self, log, monkeypatch, name):
        import repro.core
        monkeypatch.delattr(repro.core, "spans")
        monkeypatch.setitem(sys.modules, "repro.core.spans", None)
        assert metric(name).read(run_of()) is None


def test_a_ring_that_kept_the_warm_up_reads(monkeypatch):
    log = Log(monkeypatch, capacity=60)
    log.window()
    assert log.log.dropped
    assert metric("report.export_html_s").read(run_of()) == pytest.approx(
        0.007)
