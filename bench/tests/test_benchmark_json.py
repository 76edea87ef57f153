"""BENCHMARK.json names only what exists, and each cell finds every file
it needs by name."""
import json
import os
import re

import pytest

import harness

SPEC = json.load(open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = 24
    check = ((2 + 14 * cells) * (SPEC["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert check <= 43200


def test_names_units_and_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


@pytest.mark.parametrize("w", [w["name"] for w in SPEC["workloads"]])
def test_cell_finds_its_files_and_metrics(w):
    cell = harness.load_cell(w)
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.metrics("per_layer")
    for m in cell.metrics("end_to_end") + cell.metrics("per_layer"):
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))


def test_per_layer_metrics_move_a_metric_their_cells_report():
    by_cell = {w["name"]: harness.load_cell(w["name"])
               for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        for w in m.get("workloads", by_cell):
            names = {x["name"] for x in by_cell[w].metrics("end_to_end")}
            assert m["moves"] in names, (m["name"], w)


def test_four_chip_cells_are_at_most_half():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
