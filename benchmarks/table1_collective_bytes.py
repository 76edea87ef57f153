"""Paper Table 1: per-rank bytes by collective algorithm.

Validates our algorithm cost models against ground truth measured from
compiled HLO: for each primitive and communicator size N we lower an
explicit collective of payload S, parse the compiled module, and compare
the analytic per-rank wire bytes against the published ring formulas
(2(N-1)S/N for AllReduce, (N-1)S/N for AG/RS) plus the tree/hierarchical
entries the paper tabulates.
"""
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, mesh_dp
from repro.core import (hlo_parser, parse_hlo_collectives,
                        table1_allreduce_bytes, wire_bytes_per_rank)
from repro.core.reporter import format_table, human_bytes


def measured_payload(kind: str, n: int, elems: int) -> float:
    """Lower one explicit collective; return parsed payload bytes S."""
    mesh = mesh_dp(n)

    def f(x):
        if kind == "all-reduce":
            return jax.lax.psum(x, "data")
        if kind == "all-gather":
            return jax.lax.all_gather(x, "data")
        if kind == "reduce-scatter":
            return jax.lax.psum_scatter(x, "data", scatter_dimension=0,
                                        tiled=True)
        return jax.lax.all_to_all(x, "data", split_axis=0, concat_axis=0,
                                  tiled=True)

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
    # global shape chosen so the collective's logical payload S is exactly
    # elems*4 bytes per group in every case
    shape = (n * elems,) if kind in ("all-reduce", "reduce-scatter") \
        else (elems,)
    hlo = g.lower(jax.ShapeDtypeStruct(shape, jnp.float32)) \
        .compile().as_text()
    ops = [o for o in parse_hlo_collectives(hlo) if o.kind == kind]
    assert ops, f"no {kind} found"
    return float(ops[0].payload_bytes)


def main():
    t0 = time.perf_counter()
    print("== Table 1: per-rank wire bytes by algorithm "
          "(model vs published formula vs HLO payload) ==")
    rows = []
    elems = 1 << 16
    s_bytes = elems * 4
    for n in (2, 4, 8):
        for kind in ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all"):
            model = wire_bytes_per_rank(kind, s_bytes, n, "ring")
            if kind == "all-reduce":
                published = table1_allreduce_bytes(n, s_bytes, "ring")
            elif kind in ("all-gather", "reduce-scatter"):
                published = (n - 1) * s_bytes / n
            else:
                published = (n - 1) * s_bytes / (n * n)
            meas_payload = measured_payload(kind, n, elems)
            ok = abs(model - published) < 1e-6
            # HLO payload should equal S (the logical collective size)
            ok_s = abs(meas_payload - s_bytes) / s_bytes < 0.01
            rows.append([kind, n, human_bytes(s_bytes), human_bytes(model),
                         human_bytes(published),
                         human_bytes(meas_payload),
                         "OK" if (ok and ok_s) else "MISMATCH"])
            emit(f"table1/{kind}/n{n}", model,
                 f"published={published},hlo_payload={meas_payload}")
    # tree + hierarchical entries (analytic, paper-published)
    for n in (8, 16):
        for alg, role in (("tree", "other"), ("tree", "root"),
                          ("collnet", "intranode"), ("collnet", "internode")):
            v = table1_allreduce_bytes(n, s_bytes, alg, role)
            rows.append([f"all-reduce[{alg}/{role}]", n,
                         human_bytes(s_bytes), human_bytes(v), "=", "-",
                         "paper"])
            emit(f"table1/allreduce_{alg}_{role}/n{n}", v, "")
    print(format_table(rows, ["primitive", "N", "S", "model/rank",
                              "published", "HLO payload", "check"]))
    us = (time.perf_counter() - t0) * 1e6
    emit("table1/total", us, "us_total")
    assert all(r[-1] in ("OK", "paper") for r in rows), "Table 1 mismatch"
    print(f"[table1] all entries match ({us/1e6:.1f}s)")


if __name__ == "__main__":
    main()
