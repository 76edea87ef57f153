"""Paper Fig. 3: one communication matrix per collective primitive.

Runs a program that uses AllReduce, AllGather (the paper's Broadcast role)
and AllToAll, then renders each primitive's (d+1)^2 matrix separately —
showing, as the paper does, that different primitives induce different
pair-wise traffic even on the same devices.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, mesh_dp
from repro.core import monitor_fn


def main():
    mesh = mesh_dp(8)

    def program(x):
        a = jax.lax.psum(x, "data")                       # AllReduce
        b = jax.lax.all_gather(x, "data")                 # AllGather
        c = jax.lax.all_to_all(x, "data", split_axis=0,
                               concat_axis=0, tiled=True)  # AllToAll
        d = jax.lax.ppermute(x, "data",
                             [(i, (i + 1) % 8) for i in range(8)])
        return a.sum() + b.sum() + c.sum() + d.sum()

    prog = jax.shard_map(program, mesh=mesh, in_specs=P("data"),
                         out_specs=P(), check_vma=False)
    rep = monitor_fn(prog, jax.ShapeDtypeStruct((64, 256), jnp.float32),
                     mesh=mesh, name="Fig3")
    for kind, mat in sorted(rep.per_primitive.items()):
        print(rep.heatmap(kind))
        print()
        emit(f"fig3/{kind}", float(mat.sum()), "matrix_total_bytes")
    assert set(rep.per_primitive) >= {"all-reduce", "all-gather",
                                      "all-to-all", "collective-permute"}
    print("[fig3] per-primitive matrices rendered")


if __name__ == "__main__":
    main()
