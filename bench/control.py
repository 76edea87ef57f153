#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size:
the program's numbers over many seeds (the lower reading), the control's
(the reference in the next precision down: the upper reading) and, for a
training cell, the faults that step 3 of the benchmark's rules names.

  python3 bench/control.py --workload granite-3-2b.decode-b32 \
      --seeds 11,12,13 [--out chiprun_out/control]

One process reads every seed, so set-up compiles once.  Serving cells
serve enough batches at the cell's load to compare as many requests as a
run does.  The benchmark's own runs never run this.  Prints one JSON line
per seed and writes them to ``<out>/<workload>.jsonl``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def serve_readings(cell, seed, devices) -> dict:
    driver = cell.driver_module.Driver(cell, seed, devices)
    t = cell.traffic
    driver.job(units=math.ceil(t["check_requests"] / t["batch"]))
    driver.release()
    program, control = driver.control_gap()
    return {"logit_gap": program, "control.logit_gap": control}


def ddp_readings(cell, seed, devices) -> dict:
    drv = cell.driver_module
    driver = drv.Driver(cell, seed, devices)
    driver.release()
    ref = driver.reference_readings()
    out = drv.compare(driver.program_readings(), ref)
    b, n = cell.config["global_batch"], len(devices)
    faults = {"control": driver.reference_readings("bf16"),
              "half_batch": driver.reference_readings(rows=slice(0, b // 2)),
              "no_exchange": driver.reference_readings(rows=slice(0, b // n))}
    for name, got in faults.items():
        for k, v in drv.compare(got, ref).items():
            out[f"{name}.{k}"] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--out", default=os.path.join(harness.CHECKOUT,
                                                  "chiprun_out", "control"))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.CHECKOUT, "src"))
    cell = harness.load_cell(args.workload)
    devices = harness.accelerator(cell.chips)
    harness.enable_compile_cache()
    read = (serve_readings if cell.traffic["driver"] == "serve"
            else ddp_readings)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.workload + ".jsonl")
    with open(path, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            row = {"seed": seed, **read(cell, seed, devices)}
            gc.collect()
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
