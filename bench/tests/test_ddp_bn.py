"""The resnet18-bn-ddp cell on the CPU at a size a test run holds (16
64x64 images over four virtual chips), and the two readers of the views'
work counts on a synthetic span log."""
import dataclasses

import jax
import pytest

import control_buffers
import harness
from repro.core import spans
from test_spanlog import MS, Log, metric, run_of

CELL = "resnet18-bn-ddp.4chip"
SIZE = {"image_size": 64, "global_batch": 16}
TRAFFIC = {"feed_batches": 4, "units_per_cycle": 1}
# limits for this size: four images a chip make BatchNorm's statistics,
# and so float32's round-off in them, coarser than at the cell's 64
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-2, "change_gap": 0.05,
          "buffer_gap": 5e-3}


def small_cell(limits=LIMITS) -> harness.Cell:
    cell = harness.load_cell(CELL)
    return dataclasses.replace(cell, config=dict(cell.config, **SIZE),
                               traffic=dict(cell.traffic, **TRAFFIC),
                               limits=limits)


def run_small(seed=3) -> dict:
    return harness.run_cell(small_cell(), seed, 0.0, False,
                            jax.devices()[:4], harness.now(), None)


def test_sound_run_is_correct():
    result = run_small()
    assert result["correct"], result["checks"]
    assert result["checks"]["collective_bytes"]["value"] == 0.0
    assert result["checks"]["matrix_bytes"]["value"] == 0.0


@pytest.mark.parametrize("fault", [
    lambda t, axis, n: jax.lax.pmean(t, axis),     # averaged over the chips
    lambda t, axis, n: t,                          # left out
], ids=["averaged", "left_out"])
def test_buffers_not_taken_from_chip_0(monkeypatch, fault):
    from repro.train import ddp
    monkeypatch.setattr(ddp, "broadcast_from_first", fault)
    result = run_small()
    assert not result["correct"]
    gap = result["checks"]["buffer_gap"]
    assert gap["value"] > gap["limit"]


def test_control_and_faults_read_far_above_the_program():
    cell = small_cell()
    program = run_small(seed=5)["checks"]
    upper = control_buffers.readings(cell, 5)
    for k in ("grad_gap", "change_gap"):
        for fault in ("control", "half_batch", "no_exchange"):
            assert upper[f"{fault}.{k}"] >= 10 * program[k]["value"]
    assert upper["buffers_mean.buffer_gap"] >= 10 * program["buffer_gap"][
        "value"]
    assert upper["control.buffer_gap"] >= 10 * program["buffer_gap"]["value"]


# -- report.schedule_ms_per_shape and report.place_us_per_edge --------------
def reports(log, shapes, edges):
    """A warm-up report and a window of two, from 0, 100 and 200 ms: a
    1 ms decomposition inside a 4 ms matrix, a 2 ms per-primitive build,
    then an HTML export that decomposes and places again (left out)."""
    for t in (0, 100, 200):
        g = spans.new_group()
        log.add("capture.lower", t, t + 10, g)
        matrix = next(spans._ids)
        log.add("view.schedule", t + 10, t + 11, g, parent=matrix,
                counts={"view.shapes": shapes})
        log.log.append((matrix, "view.matrix", (t + 10) * MS, (t + 14) * MS,
                        None, g, {"view.edges": edges}))
        log.add("view.per_primitive", t + 14, t + 16, g,
                counts={"view.edges": edges})
        html = next(spans._ids)
        log.add("view.schedule", t + 16, t + 19, g, parent=html,
                counts={"view.shapes": shapes})
        log.add("view.matrix", t + 19, t + 20, g, parent=html,
                counts={"view.edges": edges})
        log.log.append((html, "export.html", (t + 16) * MS, (t + 22) * MS,
                        None, g, None))


@pytest.mark.parametrize("name,want", [
    # 1 ms over 4 shapes, each report
    ("report.schedule_ms_per_shape", 0.25),
    # (4 - 1) + 2 ms over 2 x 50 edges, each report
    ("report.place_us_per_edge", 50.0),
])
def test_work_count_readers(monkeypatch, name, want):
    reports(Log(monkeypatch), shapes=4, edges=50)
    assert metric(name).read(run_of()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", ["report.schedule_ms_per_shape",
                                  "report.place_us_per_edge"])
class TestWorkCountReadersReadNothing:
    def test_zero_counts(self, monkeypatch, name):
        # a report without collectives decomposes and places nothing
        reports(Log(monkeypatch), shapes=0, edges=0)
        assert metric(name).read(run_of()) is None

    def test_no_window(self, monkeypatch, name):
        reports(Log(monkeypatch), shapes=4, edges=50)
        assert metric(name).read(run_of(reports=0, cycles=0)) is None

    def test_a_program_without_the_log(self, monkeypatch, name):
        import sys

        import repro.core
        reports(Log(monkeypatch), shapes=4, edges=50)
        monkeypatch.delattr(repro.core, "spans")
        monkeypatch.setitem(sys.modules, "repro.core.spans", None)
        assert metric(name).read(run_of()) is None
