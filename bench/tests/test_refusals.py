"""Runs that must print no result: no accelerator, and a checkout that
holds only the benchmark (no system under test)."""
import os
import shutil
import subprocess
import sys

import harness

ARGS = ["--workload", "granite-3-2b.decode-b32", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_accelerator_exits_nonzero_with_no_result():
    r = _run(harness.CHECKOUT)
    assert r.returncode != 0 and r.stdout == ""
    assert "no accelerator" in r.stderr


def test_benchmark_alone_exits_nonzero_with_no_result(tmp_path):
    shutil.copy(os.path.join(harness.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench")
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout == ""
