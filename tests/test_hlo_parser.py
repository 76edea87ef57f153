"""HLO collective parsing: synthetic lines + a real compiled module."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import hlo_parser
from repro.core.hlo_parser import (HLOParseError, parse_hlo_collectives,
                                   parse_replica_groups)


class TestSyntheticLines:
    def test_explicit_groups(self):
        line = ("%psum.7 = f32[2,8]{1,0} all-reduce(%param.1), channel_id=1, "
                "replica_groups={{0,2,4,6},{1,3,5,7}}, "
                "use_global_device_ids=true, to_apply=%region_0.0")
        (op,) = parse_hlo_collectives(line)
        assert op.kind == "all-reduce"
        assert op.replica_groups == [[0, 2, 4, 6], [1, 3, 5, 7]]
        assert op.group_size == 4 and op.num_groups == 2
        assert op.result_shapes[0].bytes == 2 * 8 * 4

    def test_iota_groups(self):
        assert parse_replica_groups("replica_groups=[4,2]<=[8]") == \
            [[0, 1], [2, 3], [4, 5], [6, 7]]

    def test_iota_groups_transposed(self):
        got = parse_replica_groups("replica_groups=[2,4]<=[4,2]T(1,0)")
        assert got == [[0, 2, 4, 6], [1, 3, 5, 7]]

    def test_collective_permute_pairs(self):
        line = ("%cp = f32[4]{0} collective-permute(%p), channel_id=2, "
                "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
        (op,) = parse_hlo_collectives(line)
        assert op.source_target_pairs == [(0, 1), (1, 2), (2, 3), (3, 0)]
        assert op.wire_bytes_total() == 4 * 16

    def test_variadic_all_reduce(self):
        line = ("%ar = (f32[10]{0}, f32[512,10]{1,0}) all-reduce(%a, %b), "
                "replica_groups={{0,1,2,3}}, to_apply=%sum")
        (op,) = parse_hlo_collectives(line)
        assert op.result_bytes == (10 + 512 * 10) * 4

    def test_reduce_scatter_payload(self):
        line = ("%rs = f32[16]{0} reduce-scatter(%x), "
                "replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%sum")
        (op,) = parse_hlo_collectives(line)
        # local result is S/N -> payload is full S
        assert op.payload_bytes == 16 * 4 * 4

    def test_non_collective_lines_ignored(self):
        hlo = """
        %dot.1 = f32[8,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}
        %add.2 = f32[8]{0} add(%c, %d)
        """
        assert parse_hlo_collectives(hlo) == []

    def test_async_start_counted_once(self):
        hlo = ("%ag-start = (f32[4]{0}, f32[16]{0}) all-gather-start(%x), "
               "replica_groups={{0,1,2,3}}, dimensions={0}\n"
               "%ag-done = f32[16]{0} all-gather-done(%ag-start)")
        ops = parse_hlo_collectives(hlo)
        assert len(ops) == 1


class TestHardening:
    """Malformed attributes raise (with the op text); the channel /
    global-ids / operand attributes round-trip."""

    def test_ragged_explicit_groups_raise_with_op_text(self):
        line = ("%ar.9 = f32[8]{0} all-reduce(%p), "
                "replica_groups={{0,1,2},{3,4}}, to_apply=%sum")
        with pytest.raises(HLOParseError, match=r"ragged.*%ar\.9"):
            parse_replica_groups(line)
        with pytest.raises(HLOParseError):
            parse_hlo_collectives(line)

    def test_non_tiling_iota_raises(self):
        with pytest.raises(HLOParseError, match="do not tile"):
            parse_replica_groups("replica_groups=[4,3]<=[8]")

    def test_bad_iota_transpose_raises(self):
        with pytest.raises(HLOParseError, match="not a permutation"):
            parse_replica_groups("replica_groups=[2,4]<=[4,2]T(0,2)")

    def test_channel_and_global_ids_parsed(self):
        line = ("%ar = f32[8]{0} all-reduce(%p), channel_id=5, "
                "replica_groups={{0,1,2,3}}, use_global_device_ids=true, "
                "to_apply=%sum")
        (op,) = parse_hlo_collectives(line)
        assert op.channel_id == 5
        assert op.use_global_device_ids is True
        (plain,) = parse_hlo_collectives(
            "%ar = f32[8]{0} all-reduce(%p), replica_groups={{0,1}}, "
            "to_apply=%sum")
        assert plain.channel_id is None
        assert plain.use_global_device_ids is False

    def test_operand_names_plain(self):
        line = ("%ar = (f32[10]{0}, f32[4]{0}) all-reduce(%a, %b), "
                "replica_groups={{0,1,2,3}}, to_apply=%sum")
        (op,) = parse_hlo_collectives(line)
        assert op.operand_names == ["a", "b"]

    def test_operand_names_typed_and_tuple_shaped(self):
        """jax 0.4.x prints typed operands whose tuple shapes and layouts
        contain commas/parens -- naive splitting would yield garbage."""
        line = ("%ar = (f32[10]{0}, (s32[], f32[4])) all-reduce("
                "f32[10]{1,0} %a, (s32[], f32[4]) %b.2), "
                "replica_groups={{0,1,2,3}}, to_apply=%sum")
        (op,) = parse_hlo_collectives(line)
        assert op.operand_names == ["a", "b.2"]

    def test_async_start_operands_parsed(self):
        hlo = ("%ag-start = (f32[4]{0}, f32[16]{0}) all-gather-start(%x), "
               "replica_groups={{0,1,2,3}}, dimensions={0}\n"
               "%ag-done = f32[16]{0} all-gather-done(%ag-start)")
        (op,) = parse_hlo_collectives(hlo)
        assert op.operand_names == ["x"]


class TestRealModule:
    pytestmark = pytest.mark.compile

    def test_shard_map_collectives_roundtrip(self, mesh8):
        def f(x):
            y = jax.lax.psum(x, "data")
            z = jax.lax.all_gather(y, "model")
            return z.sum()

        g = jax.jit(jax.shard_map(f, mesh=mesh8, in_specs=P("data"),
                                  out_specs=P(), check_vma=False))
        hlo = g.lower(jnp.ones((8, 16))).compile().as_text()
        ops = parse_hlo_collectives(hlo)
        kinds = {op.kind for op in ops}
        assert "all-reduce" in kinds and "all-gather" in kinds
        ar = [op for op in ops if op.kind == "all-reduce"][0]
        assert ar.group_size == 4  # data axis
        summary = hlo_parser.summarize(ops)
        assert summary["all-reduce"]["calls"] >= 1
        assert summary["all-reduce"]["payload_bytes"] > 0

    def test_compiler_inserted_collectives_visible(self, mesh8):
        """jit-auto-sharding emits collectives the app never wrote."""
        from jax.sharding import NamedSharding

        def step(w, x):
            return ((x @ w) ** 2).mean()

        ws = NamedSharding(mesh8, P(None, "model"))
        xs = NamedSharding(mesh8, P("data", None))
        lowered = jax.jit(jax.grad(step), in_shardings=(ws, xs)).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((32, 64), jnp.float32))
        ops = parse_hlo_collectives(lowered.compile().as_text())
        assert ops, "expected compiler-inserted collectives"
