#!/usr/bin/env python3
"""The upper readings that set the limits of a DDP cell whose model keeps
state (BatchNorm's running statistics), at the cell's own size: the
control (the plain reference in bfloat16) and three faults, each compared
with the float32 reference as the cell's check compares the program, all
on the host's CPU, where the check computes its reference:
``half_batch`` and ``no_exchange`` (bench/control.py's), and
``buffers_mean``, the running statistics averaged over the chips instead of
taken from chip 0.  The program's own readings, the lower ones, are the
``checks`` of the cell's runs (``bench/run.py``) or of bench/control.py.

  python3 bench/control_buffers.py --workload resnet18-bn-ddp.4chip \
      --seeds 11,12,13 --out OUT_DIR

Needs no chip (``JAX_PLATFORMS=cpu`` will do).  Prints one JSON line per
seed and appends them to ``<out>/<workload>.buffers.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import seeding  # noqa: E402


def readings(cell, seed) -> dict:
    drv = cell.driver_module
    root = seeding.root_key(seed)
    ref = drv.reference_readings(cell, root)
    b, n = cell.config["global_batch"], cell.config["data_parallel"]
    faults = {"control": {"precision": "bf16"},
              "half_batch": {"rows": slice(0, b // 2)},
              "no_exchange": {"rows": slice(0, b // n)},
              "buffers_mean": {"buffers": "mean"}}
    out = {}
    for name, kw in faults.items():
        got = drv.reference_readings(cell, root, **kw)
        for k, v in drv.compare(got, ref).items():
            out[f"{name}.{k}"] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--out", required=True,
                    help="directory for the JSON lines")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.CHECKOUT, "src"))
    cell = harness.load_cell(args.workload)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        harness.enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.workload + ".buffers.jsonl")
    with open(path, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps({"seed": seed, **readings(cell, seed)})
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
