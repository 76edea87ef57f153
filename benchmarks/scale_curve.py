"""Fleet-scale sparse-matrix benchmark: the ``sweep --scale-curve`` engine.

The dense ``(d+1)^2`` matrix is ~2 GiB of float64 at 16384 devices; the
sparse COO path exists so fleet-scale points never allocate it.  This
benchmark pins that claim with numbers:

* **equivalence** at 1024 devices: the sparse build's ``to_dense()`` must
  equal the dense builder element-exact on the shared synthetic op stream;
* **build timings**: sparse build time at 1024 / 4096 / 16384 devices
  (dense only at 1024 -- the normalization anchor, see the guard);
* **peak memory** at 16384 devices: ``tracemalloc`` peak of the sparse
  build + link projection must stay far below the 2.1 GiB dense matrix
  (asserted < 400 MiB);
* **scale curve**: a DDP-shaped base op stream projected over
  256 -> 16384 devices must show monotonically non-decreasing bottleneck-
  link time (more devices, never a faster bottleneck at fixed payload).

Every metric lands in ``artifacts/BENCH_scale.json``; the fast CI job
asserts ``scale_curve/1024dev/sparse_over_dense`` stays within **1.5x of
the recorded baseline** -- sparse time normalized by dense time on the
same machine, so the guard compares code, not runner hardware.
"""
import json
import os
import time
import tracemalloc
import types

import numpy as np

from benchmarks.common import ARTIFACTS, emit
from repro import scale
from repro.core import comm_matrix
from repro.core.events import CollectiveOp, Shape
from repro.core.reporter import format_table

KINDS = ("all-reduce", "all-gather", "reduce-scatter",
         "collective-broadcast", "all-to-all", "collective-permute")


def synthetic_ops(num_ops: int, num_devices: int,
                  seed: int = 0) -> list[CollectiveOp]:
    """A randomized op stream shaped like a long monitored session: mixed
    kinds, groups spanning large slices of the mesh, loop-trip weights."""
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(num_ops):
        kind = KINDS[int(rng.integers(len(KINDS)))]
        elems = int(rng.integers(1, 1 << 14))
        weight = float(rng.integers(1, 65))
        if kind == "collective-permute":
            perm = rng.permutation(num_devices)
            pairs = [(int(perm[j]), int(perm[(j + 1) % len(perm)]))
                     for j in range(len(perm))]
            ops.append(CollectiveOp(
                kind=kind, name=f"op{i}",
                result_shapes=[Shape("f32", (elems,))],
                replica_groups=[], source_target_pairs=pairs,
                weight=weight))
            continue
        # partition the mesh into equal groups of a random power-of-two
        # size; all-to-all is quadratic in group size (n*(n-1) edges per
        # group), so it sweeps small groups while the ring/tree kinds span
        # up to the whole mesh
        sizes = ((4, 8, 16) if kind == "all-to-all"
                 else (8, 16, 64, num_devices))
        gsize = int(rng.choice([s for s in sizes if s <= num_devices]))
        devs = rng.permutation(num_devices)
        groups = [sorted(int(d) for d in devs[k:k + gsize])
                  for k in range(0, num_devices, gsize)]
        ops.append(CollectiveOp(
            kind=kind, name=f"op{i}",
            result_shapes=[Shape("f32", (elems,))],
            replica_groups=groups, weight=weight))
    return ops


def _time(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# tracemalloc bound for the 16k-device sparse build + projection: far under
# the ~2.1 GiB the dense (16385)^2 float64 matrix alone would need
PEAK_LIMIT_MB = 400.0


def ddp_base_ops(num_ops: int = 24, base_devices: int = 8,
                 seed: int = 2) -> list[CollectiveOp]:
    """A DDP-shaped base stream: bucketed AllReduce over the whole base
    mesh plus a metrics AllGather -- the op mix ``sweep --scale-curve``
    projects for the paper configs."""
    rng = np.random.default_rng(seed)
    group = [list(range(base_devices))]
    ops = []
    for i in range(num_ops):
        kind = "all-reduce" if i % 4 else "all-gather"
        ops.append(CollectiveOp(
            kind=kind, name=f"ddp{i}",
            result_shapes=[Shape("f32", (int(rng.integers(1 << 10,
                                                          1 << 16)),))],
            replica_groups=group, weight=float(rng.integers(1, 9))))
    return ops


def _fleet_sparse_build(ops, num_devices):
    topo = scale.fleet_topology(num_devices)
    mat = comm_matrix.matrix_for_ops(ops, num_devices, topo=topo,
                                     sparse=True)
    return comm_matrix.project_links(mat, topo), mat


def main():
    rows = []
    metrics: dict[str, float] = {}

    def record(name, value, derived=""):
        metrics[name] = float(value)
        emit(name, value, derived)

    # -- equivalence + the normalization anchor at 1024 devices ------------
    ops1k = synthetic_ops(500, 1024)
    dense = comm_matrix.matrix_for_ops(ops1k, 1024)
    sparse = comm_matrix.matrix_for_ops(ops1k, 1024, sparse=True)
    np.testing.assert_array_equal(sparse.to_dense(), dense)
    t_dense = _time(lambda: comm_matrix.matrix_for_ops(ops1k, 1024))
    t_sparse = _time(lambda: comm_matrix.matrix_for_ops(ops1k, 1024,
                                                        sparse=True))
    ratio = t_sparse / t_dense
    assert ratio <= 1.5, (
        f"sparse build is {ratio:.2f}x the dense build at 1024 devices "
        f"(acceptance bar: 1.5x -- the counting-sort coalesce should keep "
        f"COO accumulation within range of np.add.at)")
    rows.append(["1024", "500", f"{t_dense * 1e3:.1f}",
                 f"{t_sparse * 1e3:.1f}", f"{sparse.nnz:,}"])
    record("scale_curve/1024dev/dense_ms", t_dense * 1e3, "dense_np_add_at")
    record("scale_curve/1024dev/sparse_ms", t_sparse * 1e3, "coo_coalesce")
    record("scale_curve/1024dev/sparse_over_dense", ratio,
           "sparse_ms/dense_ms")
    print(f"[scale] sparse == dense element-exact at 1024 devices "
          f"({sparse.nnz:,} nnz); sparse/dense build ratio {ratio:.2f}x")

    # -- sparse-only build timings at fleet sizes --------------------------
    base = ddp_base_ops()
    for d in (1024, 4096, 16384):
        ops = scale.scale_ops(base, 8, d)
        t = _time(lambda: _fleet_sparse_build(ops, d), repeats=1)
        _, mat = _fleet_sparse_build(ops, d)
        rows.append([f"{d}", f"{len(ops)}", "-", f"{t * 1e3:.1f}",
                     f"{mat.nnz:,}"])
        record(f"scale_curve/{d}dev/sparse_build_ms", t * 1e3,
               "build_plus_link_projection")

    # -- peak memory at 16k: no dense (d+1)^2 anywhere ---------------------
    ops16k = scale.scale_ops(base, 8, 16384)
    tracemalloc.start()
    _fleet_sparse_build(ops16k, 16384)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    peak_mb = peak / 2**20
    record("scale_curve/16384dev/peak_mb", peak_mb, "tracemalloc_peak")
    assert peak_mb < PEAK_LIMIT_MB, (
        f"16384-device sparse build peaked at {peak_mb:.0f} MiB "
        f"(limit {PEAK_LIMIT_MB:.0f} MiB -- the dense matrix alone is "
        "~2100 MiB, so something materialized it)")
    print(f"[scale] 16384-device peak memory {peak_mb:.0f} MiB "
          f"(limit {PEAK_LIMIT_MB:.0f}; dense would be ~2100)")

    # -- the curve itself: bottleneck must never shrink with scale ---------
    rep = types.SimpleNamespace(compiled_ops=base, num_devices=8,
                                algorithm="ring", name="ddp_bench",
                                meta={"config": "ddp_bench"})
    points = scale.scale_curve([rep], (256, 1024, 4096, 16384))
    bns = [p.bottleneck_ms for p in points]
    assert all(b1 >= b0 * (1 - 1e-9) for b0, b1 in zip(bns, bns[1:])), (
        f"bottleneck-link ms must grow monotonically with fleet size, "
        f"got {bns}")
    for p in points:
        record(f"scale_curve/curve/{p.devices}dev/bottleneck_ms",
               p.bottleneck_ms, p.bottleneck_link)
        record(f"scale_curve/curve/{p.devices}dev/overlap_ms", p.overlap_ms,
               "max(ici,dcn)")
    print("[scale] curve bottleneck-link ms monotone over "
          + " -> ".join(f"{p.devices}" for p in points))
    print(scale.scale_table(points))

    print(format_table(rows, ["devices", "ops", "dense ms", "sparse ms",
                              "nnz"]))
    _baseline_guard(metrics)      # vs the recorded artifact, pre-overwrite

    out = os.path.join(ARTIFACTS, "BENCH_scale.json")
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(out, "w") as f:
        json.dump({"benchmark": "scale_curve", "metrics": metrics}, f,
                  indent=2, sort_keys=True)
    print(f"[scale] wrote {out}")


def _baseline_guard(metrics: dict[str, float]) -> None:
    """Fast-CI perf guard: the sparse build must stay within 1.5x of the
    recorded ``artifacts/BENCH_scale.json`` baseline on the 1024-device
    cell, normalized by the dense build's time on the SAME machine."""
    path = os.path.join(ARTIFACTS, "BENCH_scale.json")
    if not os.path.exists(path):
        print("[scale] no recorded baseline; skipping the 1.5x guard")
        return
    try:
        with open(path) as f:
            base = json.load(f)["metrics"]
        base_ratio = base["scale_curve/1024dev/sparse_over_dense"]
    except (KeyError, ValueError, OSError):
        print("[scale] unreadable baseline; skipping the 1.5x guard")
        return
    cur_ratio = metrics["scale_curve/1024dev/sparse_over_dense"]
    rel = cur_ratio / base_ratio
    assert rel <= 1.5, (
        f"sparse build regressed to {rel:.2f}x the recorded baseline on "
        f"the 1024-device cell (sparse/dense {cur_ratio:.2f} now vs "
        f"{base_ratio:.2f} recorded; allowed: 1.5x)")
    print(f"[scale] baseline guard OK: {rel:.2f}x the recorded "
          f"dense-normalized sparse time (limit 1.5x)")


if __name__ == "__main__":
    main()
