"""The benchmark harness: finds a cell by its name, sets it up, runs its
window of job and monitor cycles, reads its metrics and checks what the
timed path produced.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

  bench/configs/<config>.json     the configuration as it is run
  bench/configs/<config>.py       its build, weights, operation and byte
                                  counts, and its plain reference
  bench/traffic/<traffic>.json    the traffic mix; its "driver" names
  bench/drivers/<driver>.py       the job and monitor segments of that kind
                                  of job
  bench/limits/<workload>.json    the limits of the cell's comparison
  bench/metrics/<metric>.py       ``read(run)``: the metric, or None

A window is a loop of cycles.  A cycle is a job segment (the watched job
does ``units_per_cycle`` units, timed to ``block_until_ready``) and then a
monitor segment (capture, ``report()``, the report written as JSON and
HTML).  The window closes at the end of the first cycle that ends
``--seconds`` after it opened.  With ``--trace 1`` the profiler records the
job segments of the first ``TRACE_CYCLES`` cycles; monitor segments always
run with it off.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(CHECKOUT, ".bench_cache", "jax")
TRACE_CYCLES = 2

now = time.perf_counter


class Refused(Exception):
    """A run that must print no result (no chip, no such cell)."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise Refused(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: str
    chips: int
    spec: dict                  # the whole BENCHMARK.json
    config: dict
    config_module: object
    traffic: dict
    driver_module: object
    limits: dict

    def metrics(self, section: str) -> list[dict]:
        """The metrics of ``section`` ("end_to_end" or "per_layer") that
        this cell reports."""
        e2e = [m for m in self.spec["end_to_end"] if self._lists(m)]
        if section == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if self._lists(m) and m["moves"] in names]

    def _lists(self, metric: dict) -> bool:
        return ("workloads" not in metric
                or self.workload in metric["workloads"])


def load_cell(workload: str, spec: dict | None = None) -> Cell:
    """The cell ``workload`` of ``spec`` (default: the checkout's
    BENCHMARK.json) with every file it names."""
    if spec is None:
        path = os.path.join(CHECKOUT, "BENCHMARK.json")
        if not os.path.exists(path):
            raise Refused(f"no {path}")
        spec = _json(path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _json(os.path.join(CHECKOUT, conf["file"]))
    stem = os.path.splitext(os.path.join(CHECKOUT, conf["file"]))[0]
    traffic = _json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    return Cell(
        workload=workload, chips=w["chips"], spec=spec, config=config,
        config_module=load_module(stem + ".py", "bench_config_"
                                  + conf["name"].replace("-", "_")),
        traffic=traffic,
        driver_module=load_module(
            os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
            "bench_driver_" + traffic["driver"]),
        limits=_json(os.path.join(BENCH, "limits", workload + ".json")))


@dataclasses.dataclass
class Run:
    """What a window measured; the metric readers take it."""
    cell: Cell
    peaks: dict | None
    setup_s: float = 0.0
    job_s: float = 0.0
    monitor_s: float = 0.0
    cycles: int = 0
    reports: int = 0
    compiles: int = 0
    counts: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    traced_job_s: float = 0.0
    traced_counts: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None

    def per_report(self, key: str) -> float | None:
        """The mean over reports of one monitor span."""
        if not self.spans:
            return None
        return sum(s[key] for s in self.spans) / len(self.spans)


def _add(into: dict, counts: dict) -> None:
    for k, v in counts.items():
        into[k] = into.get(k, 0) + v


class CompileCounter:
    """Counts compilations that missed the persistent cache."""

    def __init__(self):
        import jax
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is kept, however fast it compiled, so only a checkout's first
    run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def window(driver, run: Run, seconds: float, out_dir: str,
           trace_dir: str | None, counter: CompileCounter) -> None:
    import jax

    import trace_reduce
    misses0 = counter.misses
    opened = now()
    while True:
        traced = trace_dir is not None and run.cycles < TRACE_CYCLES
        if traced:
            jax.profiler.start_trace(os.path.join(trace_dir, str(run.cycles)),
                                     profiler_options=trace_reduce.options())
        t0 = now()
        counts = driver.job()
        t1 = now()
        if traced:
            jax.profiler.stop_trace()
            run.traced_job_s += t1 - t0
            _add(run.traced_counts, counts)
        run.job_s += t1 - t0
        _add(run.counts, counts)
        t2 = now()
        run.spans.append(driver.monitor(out_dir, now))
        run.monitor_s += now() - t2
        log(f"[bench] cycle {run.cycles}: job {t1 - t0:.4f} s, monitor "
            f"{now() - t2:.4f} s")
        run.reports += 1
        run.cycles += 1
        if now() - opened >= seconds:
            break
    run.compiles = counter.misses - misses0


def read_metrics(run: Run, section: str) -> dict:
    out = {}
    for m in run.cell.metrics(section):
        reader = load_module(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            if not math.isfinite(value):
                raise ValueError(f"metric {m['name']} read {value}")
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """The peak bytes in use on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_process: float, peaks: dict | None) -> dict:
    """Set up, run the window, check; returns the result line's object."""
    import trace_reduce
    counter = CompileCounter()
    log(f"[bench] {cell.workload}: seed {seed}, {seconds} s window, trace "
        f"{int(trace)}; device {device_info(devices)}")
    work = tempfile.mkdtemp(prefix="bench-")
    try:
        out_dir = os.path.join(work, "reports")
        os.makedirs(out_dir)
        log(f"[bench] devices up at {now() - t_process:.3f} s")
        driver = cell.driver_module.Driver(cell, seed, devices)
        log(f"[bench] built at {now() - t_process:.3f} s, "
            f"{counter.misses} compiles")
        # warm-up cycle: every shape the window uses, and one report
        driver.job(units=1)
        log(f"[bench] warm-up unit done at {now() - t_process:.3f} s, "
            f"{counter.misses} compiles")
        driver.monitor(out_dir, now)
        run = Run(cell=cell, peaks=peaks)
        run.setup_s = now() - t_process
        log(f"[bench] set-up {run.setup_s:.3f} s")
        trace_dir = os.path.join(work, "trace") if trace else None
        window(driver, run, seconds, out_dir, trace_dir, counter)
        log(f"[bench] window: {run.cycles} cycles, job {run.job_s:.3f} s, "
            f"monitor {run.monitor_s:.3f} s, {run.compiles} compiles")
        device = device_info(devices)
        device["memory_peak_bytes"] = memory_peak(devices)
        if trace:
            run.trace = trace_reduce.reduce_dir(trace_dir, run.traced_job_s)
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
        driver.release()
        t0 = now()
        checks = driver.check()
        log(f"[bench] check {now() - t0:.3f} s")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    metrics = read_metrics(run, "per_layer" if trace else "end_to_end")
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": int(run.counts["attempted"]),
              "failed": 0, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = run.trace["breakdown"]
    result["window"] = {"cycles": run.cycles, "reports": run.reports,
                        "job_s": run.job_s, "monitor_s": run.monitor_s,
                        "compiles": run.compiles}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def accelerator(chips: int):
    """The first ``chips`` accelerator devices; refuses a host without
    them (never falls back to the CPU)."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise Refused("no accelerator: jax's default backend is the CPU")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; jax finds "
                      f"{len(devices)}")
    return devices[:chips]


def main(argv, t_process: float) -> int:
    args = parse_args(argv)
    try:
        sys.path.insert(0, os.path.join(CHECKOUT, "src"))
        sys.path.insert(0, BENCH)
        import repro  # noqa: F401  (the system under test must be there)
        cell = load_cell(args.workload)
        devices = accelerator(cell.chips)
        from peaks import peaks_for
        peaks = peaks_for(devices[0].device_kind)
    except (Refused, ImportError, KeyError, OSError) as e:
        log(f"error: {e}")
        return 2
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, t_process, peaks)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
