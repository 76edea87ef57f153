#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of
standard output:

  python3 bench/run.py --workload granite-3-2b.decode-b32 --seed 7 \
      --seconds 30 --trace 0

The cell, its configuration, traffic, limits and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/harness.py``).  Exits 2, printing no
result, where jax finds no accelerator, fewer chips than the cell asks
for, or no system under test beside the benchmark.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_PROCESS))
