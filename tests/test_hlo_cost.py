"""Loop-aware HLO cost extraction (trip-count multipliers)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.hlo_cost import (HloAnalyzer, analyze_hlo,
                                 computation_multipliers, split_computations,
                                 top_ops)


def _compile(f, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(f).lower(*args).compile().as_text()


class TestMultipliers:
    @pytest.mark.compile
    def test_nested_scan_flops_exact(self):
        def f(x):
            def outer(c, _):
                def body(c, _):
                    return c @ x + 1.0, None
                c, _ = jax.lax.scan(body, c, None, length=8)
                return c, None
            out, _ = jax.lax.scan(outer, x, None, length=4)
            return out.sum()

        hc = analyze_hlo(_compile(f, (64, 64)))
        expected = 2 * 64**3 * 32
        assert hc.flops == pytest.approx(expected, rel=0.05)

    @pytest.mark.compile
    def test_no_loop_flops_exact(self):
        hc = analyze_hlo(_compile(lambda a, b: a @ b, (32, 48), (48, 16)))
        assert hc.flops == pytest.approx(2 * 32 * 48 * 16, rel=0.01)

    @pytest.mark.compile
    def test_collectives_weighted_by_trip_count(self, mesh_dp):
        def g(x):
            def body(c, _):
                return jax.lax.psum(c, "data") * 0.1, None
            c, _ = jax.lax.scan(body, x, None, length=16)
            return c

        gg = jax.jit(jax.shard_map(g, mesh=mesh_dp, in_specs=P("data"),
                                   out_specs=P("data"), check_vma=False))
        hlo = gg.lower(jax.ShapeDtypeStruct((8, 64), jnp.float32)) \
            .compile().as_text()
        s = analyze_hlo(hlo).collective_summary()
        assert s["all-reduce"]["calls"] == 16

    def test_synthetic_multiplier_graph(self):
        hlo = """
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %tuple = (s32[], f32[4]) tuple(%c, %p)
  %while.1 = (s32[], f32[4]) while(%tuple), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %gte = f32[4]{0} get-tuple-element(%while.1), index=1
}
%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  ROOT %r = (s32[], f32[4]) tuple(%i, %x)
}
%cond (t2: (s32[], f32[4])) -> pred[] {
  %t2 = (s32[], f32[4]) parameter(0)
  ROOT %lt = pred[] compare(%i2, %n), direction=LT
}
"""
        comps, entry = split_computations(hlo)
        mult = computation_multipliers(comps, entry)
        assert mult["main"] == 1.0
        assert mult["body"] == 10.0
        assert mult["cond"] == 11.0

    def test_trip_count_inferred_without_annotation(self):
        """No known_trip_count backend_config (older jaxlibs / other
        pipelines): the trip count is statically inferred from the
        compare(iter, constant) condition + body increment + initializer,
        including the typed-operand spelling jax 0.4.x prints."""
        hlo = """
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %c0 = s32[] constant(2)
  %copy.1 = s32[] copy(s32[] %c0)
  %tuple = (s32[], f32[4]) tuple(s32[] %copy.1, f32[4]{0} %p)
  %while.1 = (s32[], f32[4]) while((s32[], f32[4]) %tuple), condition=%cond, body=%body
  ROOT %gte = f32[4]{0} get-tuple-element((s32[], f32[4]) %while.1), index=1
}
%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]) %t), index=0
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %i, s32[] %one)
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]) %t), index=1
  ROOT %r = (s32[], f32[4]) tuple(s32[] %next, f32[4]{0} %x)
}
%cond (t2: (s32[], f32[4])) -> pred[] {
  %t2 = (s32[], f32[4]) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], f32[4]) %t2), index=0
  %n = s32[] constant(9)
  ROOT %lt = pred[] compare(s32[] %i2, s32[] %n), direction=LT
}
"""
        comps, entry = split_computations(hlo)
        mult = computation_multipliers(comps, entry)
        # iter runs 2,3,...,8 -> 7 trips, inferred with no annotation
        assert mult["body"] == 7.0
        assert mult["cond"] == 8.0

    def test_trip_count_inference_flipped_compare(self):
        """constant-on-the-left compare still infers (direction flipped)."""
        hlo = """
ENTRY %main (p: f32[4]) -> (s32[], f32[4]) {
  %p = f32[4]{0} parameter(0)
  %c0 = s32[] constant(0)
  %tuple = (s32[], f32[4]) tuple(s32[] %c0, f32[4]{0} %p)
  ROOT %while.1 = (s32[], f32[4]) while((s32[], f32[4]) %tuple), condition=%cond, body=%body
}
%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]) %t), index=0
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %one, s32[] %i)
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]) %t), index=1
  ROOT %r = (s32[], f32[4]) tuple(s32[] %next, f32[4]{0} %x)
}
%cond (t2: (s32[], f32[4])) -> pred[] {
  %t2 = (s32[], f32[4]) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], f32[4]) %t2), index=0
  %n = s32[] constant(5)
  ROOT %gt = pred[] compare(s32[] %n, s32[] %i2), direction=GT
}
"""
        comps, entry = split_computations(hlo)
        mult = computation_multipliers(comps, entry)
        assert mult["body"] == 5.0

    def test_unbounded_loop_defaults_to_one(self):
        """A data-dependent bound must not be guessed: body counts once."""
        hlo = """
ENTRY %main (p: s32[]) -> (s32[], s32[]) {
  %p = s32[] parameter(0)
  %c0 = s32[] constant(0)
  %tuple = (s32[], s32[]) tuple(s32[] %c0, s32[] %p)
  ROOT %while.1 = (s32[], s32[]) while((s32[], s32[]) %tuple), condition=%cond, body=%body
}
%body (t: (s32[], s32[])) -> (s32[], s32[]) {
  %t = (s32[], s32[]) parameter(0)
  %i = s32[] get-tuple-element((s32[], s32[]) %t), index=0
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %i, s32[] %one)
  %lim = s32[] get-tuple-element((s32[], s32[]) %t), index=1
  ROOT %r = (s32[], s32[]) tuple(s32[] %next, s32[] %lim)
}
%cond (t2: (s32[], s32[])) -> pred[] {
  %t2 = (s32[], s32[]) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], s32[]) %t2), index=0
  %lim2 = s32[] get-tuple-element((s32[], s32[]) %t2), index=1
  ROOT %lt = pred[] compare(s32[] %i2, s32[] %lim2), direction=LT
}
"""
        comps, entry = split_computations(hlo)
        mult = computation_multipliers(comps, entry)
        assert mult["body"] == 1.0

    def test_early_exit_condition_not_guessed(self):
        """compare feeding an and() root = extra exit conditions; the
        compare bound is an upper limit, not the trip count."""
        hlo = """
ENTRY %main (p: pred[]) -> (s32[], pred[]) {
  %p = pred[] parameter(0)
  %c0 = s32[] constant(0)
  %tuple = (s32[], pred[]) tuple(s32[] %c0, pred[] %p)
  ROOT %while.1 = (s32[], pred[]) while((s32[], pred[]) %tuple), condition=%cond, body=%body
}
%body (t: (s32[], pred[])) -> (s32[], pred[]) {
  %t = (s32[], pred[]) parameter(0)
  %i = s32[] get-tuple-element((s32[], pred[]) %t), index=0
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %i, s32[] %one)
  %f = pred[] get-tuple-element((s32[], pred[]) %t), index=1
  ROOT %r = (s32[], pred[]) tuple(s32[] %next, pred[] %f)
}
%cond (t2: (s32[], pred[])) -> pred[] {
  %t2 = (s32[], pred[]) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], pred[]) %t2), index=0
  %n = s32[] constant(100)
  %lt = pred[] compare(s32[] %i2, s32[] %n), direction=LT
  %flag = pred[] get-tuple-element((s32[], pred[]) %t2), index=1
  ROOT %and = pred[] and(pred[] %lt, pred[] %flag)
}
"""
        comps, entry = split_computations(hlo)
        mult = computation_multipliers(comps, entry)
        assert mult["body"] == 1.0

    def test_hidden_increment_not_guessed(self):
        """No top-level constant increment of the induction variable (e.g.
        folded into a fusion): refuse to assume step=1."""
        hlo = """
ENTRY %main (p: f32[4]) -> (s32[], f32[4]) {
  %p = f32[4]{0} parameter(0)
  %c0 = s32[] constant(0)
  %tuple = (s32[], f32[4]) tuple(s32[] %c0, f32[4]{0} %p)
  ROOT %while.1 = (s32[], f32[4]) while((s32[], f32[4]) %tuple), condition=%cond, body=%body
}
%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]) %t), index=0
  %next = s32[] fusion(s32[] %i), kind=kLoop, calls=%inc_fusion
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]) %t), index=1
  ROOT %r = (s32[], f32[4]) tuple(s32[] %next, f32[4]{0} %x)
}
%inc_fusion (q: s32[]) -> s32[] {
  %q = s32[] parameter(0)
  %two = s32[] constant(2)
  ROOT %a = s32[] add(s32[] %q, s32[] %two)
}
%cond (t2: (s32[], f32[4])) -> pred[] {
  %t2 = (s32[], f32[4]) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], f32[4]) %t2), index=0
  %n = s32[] constant(100)
  ROOT %lt = pred[] compare(s32[] %i2, s32[] %n), direction=LT
}
"""
        comps, entry = split_computations(hlo)
        mult = computation_multipliers(comps, entry)
        assert mult["body"] == 1.0


class TestBytesModel:
    @pytest.mark.compile
    def test_dus_fusion_counts_slice_not_buffer(self):
        """A scan writing 1-slice into a big stacked carry must charge the
        slice (the DUS buffer operand is aliased)."""
        def f(x):
            def body(c, _):
                return c * 1.5, c
            _, ys = jax.lax.scan(body, x, None, length=32)
            return ys.sum()

        hlo = _compile(f, (128, 128))
        hc = analyze_hlo(hlo)
        # if the full (32,128,128) buffer were charged per step, bytes would
        # exceed 32 steps * 32*128*128*4 * 2 = 128 MiB; slice-aware ~ a few MiB
        assert hc.bytes_hbm < 60e6, hc.bytes_hbm / 1e6

    @pytest.mark.compile
    def test_top_ops_returns_sorted(self):
        hlo = _compile(lambda a, b: jax.nn.relu(a @ b), (64, 64), (64, 64))
        rows = top_ops(hlo, 5, by="flops")
        assert rows and rows[0][0] >= rows[-1][0]

    def test_analyzer_handles_empty(self):
        hc = analyze_hlo("")
        assert hc.flops == 0 and hc.collectives == []
