"""Trace-time interception (the LD_PRELOAD analogue)."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import CollectiveInterceptor, intercept

import pytest

pytestmark = pytest.mark.compile   # whole module drives XLA compiles


def _traced_program(mesh):
    def f(x):
        y = jax.lax.psum(x, "data")
        z = jax.lax.all_gather(y, "model")
        w = jax.lax.ppermute(x, "data", [(i, (i + 1) % 4) for i in range(4)])
        return y.sum() + z.sum() + w.sum()

    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=P(), check_vma=False))


class TestInterceptor:
    def test_captures_collectives(self, mesh8):
        with CollectiveInterceptor(mesh=mesh8) as icpt:
            _traced_program(mesh8).lower(jnp.ones((8, 16)))
        prims = [e.primitive for e in icpt.events]
        assert "psum" in prims and "all_gather" in prims \
            and "ppermute" in prims

    def test_axis_sizes_resolved(self, mesh8):
        with CollectiveInterceptor(mesh=mesh8) as icpt:
            _traced_program(mesh8).lower(jnp.ones((8, 16)))
        psum = [e for e in icpt.events if e.primitive == "psum"][0]
        assert psum.axis_size == 4      # data axis
        ag = [e for e in icpt.events if e.primitive == "all_gather"][0]
        assert ag.axis_size == 2        # model axis

    def test_payload_bytes(self, mesh8):
        with CollectiveInterceptor(mesh=mesh8) as icpt:
            _traced_program(mesh8).lower(jnp.ones((8, 16)))
        psum = [e for e in icpt.events if e.primitive == "psum"][0]
        # per-shard (2,16) f32
        assert psum.payload_bytes == 2 * 16 * 4

    def test_no_capture_outside_context(self, mesh8):
        prog = _traced_program(mesh8)
        with CollectiveInterceptor(mesh=mesh8) as icpt:
            pass
        prog.lower(jnp.ones((8, 16)))  # traced after exit
        assert icpt.events == []

    def test_nested_interceptors_both_see(self, mesh8):
        with CollectiveInterceptor(mesh=mesh8) as outer:
            with CollectiveInterceptor(mesh=mesh8) as inner:
                _traced_program(mesh8).lower(jnp.ones((8, 16)))
        assert len(outer.events) == len(inner.events) > 0

    def test_numerics_unchanged(self, mesh8):
        x = jnp.arange(128.0).reshape(8, 16)
        prog = _traced_program(mesh8)
        expected = prog(x)
        with intercept(mesh8):
            got = jax.jit(jax.shard_map(
                lambda v: jax.lax.psum(v, "data").sum(), mesh=mesh8,
                in_specs=P("data"), out_specs=P(), check_vma=False))(x)
        assert jnp.isfinite(got)
        assert jnp.allclose(prog(x), expected)

    def test_summary_uses_nccl_names(self, mesh8):
        with CollectiveInterceptor(mesh=mesh8) as icpt:
            _traced_program(mesh8).lower(jnp.ones((8, 16)))
        s = icpt.summary()
        assert "AllReduce" in s and "AllGather" in s and "SendRecv" in s
        assert s["AllReduce"]["calls"] >= 1
