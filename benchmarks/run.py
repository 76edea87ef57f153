"""Benchmark harness: one entry per paper table/figure + the roofline report.

The CLI front door (preferred):

  python -m repro bench                              # all
  python -m repro bench table3                       # one

Direct invocation still works:

  PYTHONPATH=src python -m benchmarks.run [names...]

Benchmarks that record a committed ``artifacts/BENCH_*.json`` baseline get
a **baseline-vs-current** comparison table at the end of the run: the
recorded metrics are snapshotted before any benchmark overwrites its
artifact, and each shared metric prints baseline / current / ratio.
"""
import glob
import json
import os
import sys
import time
import traceback

from benchmarks import common

BENCHES = ("table1", "table2", "table3", "fig3", "links", "roofline",
           "scale", "trace")

_MODS = {
    "table1": "benchmarks.table1_collective_bytes",
    "table2": "benchmarks.table2_gnmt",
    "table3": "benchmarks.table3_resnet_bucketing",
    "fig3": "benchmarks.fig3_per_primitive",
    "links": "benchmarks.link_utilization",
    "roofline": "benchmarks.roofline_table",
    "scale": "benchmarks.scale_curve",
    "trace": "benchmarks.trace_ingest",
}


def _read_bench_metrics() -> dict[str, float]:
    """Every metric in the committed ``artifacts/BENCH_*.json`` files."""
    merged: dict[str, float] = {}
    for path in sorted(glob.glob(os.path.join(common.ARTIFACTS,
                                              "BENCH_*.json"))):
        try:
            with open(path) as f:
                merged.update(json.load(f).get("metrics", {}))
        except (ValueError, OSError):
            continue
    return merged


def _comparison_table(baseline: dict[str, float],
                      current: dict[str, float]) -> None:
    """Print metric / baseline / current / ratio for every metric present
    both before and after the run (new metrics are listed as such)."""
    from repro.core.reporter import format_table

    shared = sorted(set(baseline) & set(current))
    fresh = sorted(set(current) - set(baseline))
    if not shared and not fresh:
        return
    rows = []
    for m in shared:
        b, c = baseline[m], current[m]
        ratio = c / b if b else float("inf")
        rows.append([m, f"{b:.3f}", f"{c:.3f}", f"{ratio:.2f}x"])
    for m in fresh:
        rows.append([m, "-", f"{current[m]:.3f}", "new"])
    print("\n== baseline vs current (BENCH_*.json) ==")
    print(format_table(rows, ["metric", "baseline", "current", "ratio"]))


def run_one(name: str) -> bool:
    import importlib
    mod = _MODS[name]
    print(f"\n{'='*72}\n## {name} ({mod})\n{'='*72}")
    t0 = time.perf_counter()
    try:
        importlib.import_module(mod).main()
        print(f"[{name}] PASS in {time.perf_counter()-t0:.1f}s")
        return True
    except Exception:
        traceback.print_exc()
        print(f"[{name}] FAIL")
        return False


def main(names=None) -> int:
    if names is None:               # direct invocation: read our own argv
        names = sys.argv[1:]
    todo = list(names) or list(BENCHES)
    unknown = [n for n in todo if n not in BENCHES]
    if unknown:
        print(f"unknown benchmark(s) {unknown}; known: {list(BENCHES)}",
              file=sys.stderr)
        return 2
    baseline = _read_bench_metrics()      # before any artifact overwrite
    results = {name: run_one(name) for name in todo}
    common.flush_csv("artifacts/benchmarks.csv")
    _comparison_table(baseline, _read_bench_metrics())
    print("\n== benchmark summary ==")
    for name, ok in results.items():
        print(f"  {name:10s} {'PASS' if ok else 'FAIL'}")
    return 0 if all(results.values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
