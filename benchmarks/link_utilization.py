"""Per-link utilization: where the bytes of one collective actually travel.

Beyond-paper benchmark for the physical-link subsystem: runs the same
data-parallel all-reduce program on a single-pod mesh and on a two-pod
(DCN-joined) mesh, then projects each algorithm's communication matrix onto
the physical ICI / DCN links.  The table shows what the logical ``(d+1)^2``
matrix hides:

* ring edges between non-neighbour torus coordinates become multi-hop ICI
  transit traffic (link bytes > matrix bytes),
* a hierarchical all-reduce puts only the ``S/m`` shard exchange on DCN
  uplinks, while ring/tree across pods push full per-rank payloads through
  the slow tier -- visible directly in the bottleneck-link milliseconds,
* the tier-overlap bound (ici ∥ dcn) never exceeds the serialized
  collective time, and only the hierarchical algorithm keeps both tiers
  busy at once.

The run doubles as the CI perf smoke: every emitted metric lands in
``artifacts/BENCH_link.json`` so the perf trajectory is machine-readable.
"""
import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import ARTIFACTS, emit
from repro.compat import make_mesh
from repro.core import monitor_fn
from repro.core.reporter import format_table, human_bytes


def _program(mesh):
    def step(x):
        g = jax.lax.psum(x, tuple(mesh.axis_names))
        return (x * g).sum()

    return jax.shard_map(step, mesh=mesh,
                         in_specs=P(mesh.axis_names[0]),
                         out_specs=P(), check_vma=False)


def main():
    meshes = {
        "8 (one pod)": make_mesh((8,), ("data",)),
        "2x2x2 (two pods)": make_mesh((2, 2, 2), ("pod", "data", "model")),
    }
    rows = []
    raw: dict[tuple, dict] = {}          # (mesh, alg) -> unrounded seconds
    metrics: dict[str, float] = {}

    def record(name, value, derived=""):
        metrics[name] = float(value)
        emit(name, value, derived)

    for mesh_name, mesh in meshes.items():
        rep = monitor_fn(_program(mesh),
                         jax.ShapeDtypeStruct((8, 4096), jnp.float32),
                         mesh=mesh, name=f"links@{mesh_name}")
        for alg in ("ring", "tree", "hierarchical"):
            lu = rep.link_utilization(alg)
            bn = lu.bottleneck()
            matrix_bytes = rep.view(alg).matrix[1:, 1:].sum()
            ici_s, dcn_s = rep.collective_seconds_split(alg)
            overlap_ms = max(ici_s, dcn_s) * 1e3
            serial_ms = (ici_s + dcn_s) * 1e3
            raw[(mesh_name, alg)] = {
                "ici_s": ici_s, "dcn_s": dcn_s,
                "bottleneck_s": bn[1] if bn else 0.0}
            rows.append([
                mesh_name, alg,
                human_bytes(matrix_bytes),
                human_bytes(lu.total_bytes("ici")),
                human_bytes(lu.total_bytes("dcn")),
                bn[0].name if bn else "-",
                f"{bn[1] * 1e3:.4f}" if bn else "-",
                f"{overlap_ms:.4f}",
                f"{serial_ms:.4f}",
            ])
            record(f"links/{mesh_name}/{alg}/ici_bytes",
                   lu.total_bytes("ici"), "physical_link_bytes")
            record(f"links/{mesh_name}/{alg}/dcn_bytes",
                   lu.total_bytes("dcn"), "physical_link_bytes")
            record(f"links/{mesh_name}/{alg}/bottleneck_ms",
                   (bn[1] * 1e3) if bn else 0.0, "contention_bound")
            record(f"links/{mesh_name}/{alg}/overlap_ms",
                   overlap_ms, "tier_overlap_bound")
            record(f"links/{mesh_name}/{alg}/serialized_ms",
                   serial_ms, "serialized_collective_time")
    print(format_table(rows, [
        "mesh", "algorithm", "matrix bytes", "ICI link bytes",
        "DCN link bytes", "bottleneck link", "bottleneck ms",
        "overlap ms", "serialized ms"]))

    # invariants the table is meant to exhibit (asserted on the raw
    # seconds, not the 4-decimal table strings)
    by_key = {(r[0], r[1]): r for r in rows}
    hier = by_key[("2x2x2 (two pods)", "hierarchical")]
    assert hier[4] != "0 B", "hierarchical must use DCN on a two-pod mesh"
    assert raw[("2x2x2 (two pods)", "hierarchical")]["bottleneck_s"] <= \
        raw[("2x2x2 (two pods)", "ring")]["bottleneck_s"], \
        "hierarchical must not be slower than ring across DCN"
    one_pod = [r for r in rows if r[0] == "8 (one pod)"]
    assert all(r[4] == "0 B" for r in one_pod), "no DCN traffic inside a pod"
    for v in raw.values():
        assert max(v["ici_s"], v["dcn_s"]) <= v["ici_s"] + v["dcn_s"] + 1e-15, \
            "tier-overlap bound must not exceed the serialized time"
    h = raw[("2x2x2 (two pods)", "hierarchical")]
    assert h["ici_s"] > 0 and h["dcn_s"] > 0, \
        "hierarchical must keep both tiers busy (strict overlap win)"
    print("[links] per-link utilization + overlap invariants hold")

    out = os.path.join(ARTIFACTS, "BENCH_link.json")
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(out, "w") as f:
        json.dump({"benchmark": "link_utilization", "metrics": metrics}, f,
                  indent=2, sort_keys=True)
    print(f"[links] wrote {out}")


if __name__ == "__main__":
    main()
