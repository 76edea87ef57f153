"""Compiles for a described (not attached) TPU v5e: the chip's own compiler
accepts the main path's kernels and the paper's DDP step at real widths.

Nothing runs, so these say nothing about results or times; they catch what
interpret mode cannot (tiling, VMEM limits, unsupported lowering) before a
chip is involved.  The topology is described inside a fixture, never while
a module is imported: only one process at a time may load the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

pytestmark = pytest.mark.compile

# granite-3-2b attention: 32 heads of 64 (kv heads pre-expanded by the
# model before the kernel), a 4-request prefill batch
GRANITE_HEADS, GRANITE_DH, PREFILL_BATCH = 32, 64, 4
# ResNet-18 (200 classes) parameters x 4 B in two 25 MiB gradient buckets,
# plus the 4-byte loss pmean
DDP_PSUM_BYTES = 11_269_640 * 4 + 4
# the published layout (7x7 stem, BatchNorm on the shortcuts too)
DDP_BN_PSUM_BYTES = 11_279_112 * 4 + 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described chip's executable cannot be read back from the persistent
    # cache without the chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attend(q, k, v):
    from repro.kernels.flash_attention import ops
    return ops.attend(q, k, v, force="pallas")


# 3584: prefill-b4's prompts; 1000: whole-sequence blocks
@pytest.mark.parametrize("seq", [512, 1000, 3584])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_prefill_compiles(one_chip, seq, dtype):
    x = jax.ShapeDtypeStruct((PREFILL_BATCH, seq, GRANITE_HEADS, GRANITE_DH),
                             dtype, sharding=one_chip)
    compiled = jax.jit(_attend).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # at least the logical output (the chip's layout pads 1000 to 1024)
    assert compiled.memory_analysis().output_size_in_bytes >= (
        PREFILL_BATCH * seq * GRANITE_HEADS * GRANITE_DH
        * jnp.dtype(dtype).itemsize)


def test_flash_attention_decode_prompt_compiles(one_chip):
    """decode-b32's prefill: 32 prompts of 512, one 512 x 512 block."""
    x = jax.ShapeDtypeStruct((32, 512, GRANITE_HEADS, GRANITE_DH),
                             jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(_attend).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_gradient_compiles(one_chip):
    """The custom VJP's backward (the chunked XLA path) compiles next to
    the kernel's forward."""
    x = jax.ShapeDtypeStruct((PREFILL_BATCH, 512, GRANITE_HEADS, GRANITE_DH),
                             jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(_attend(q, k, v) ** 2)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    compiled = step.lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_resnet18_ddp_allreduce_bytes(topo):
    """The paper's DDP ResNet-18 step on a 4-chip data mesh: the bytes of
    the all-reduces the TPU compiler emits equal the traced psums'."""
    from repro.configs import paper
    from repro.core import MonitorSession, V5E
    from repro.train import ddp

    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    model = paper.resnet18_model(paper.RESNET_DATA["num_classes"])
    step = ddp.make_ddp_train_step(model.loss_fn, mesh, mode="bucketed",
                                   bucket_mb=25.0)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
        model.shapes())
    b, side = paper.RESNET_DATA["global_batch"], paper.RESNET_DATA["image_size"]
    batch = {"images": jax.ShapeDtypeStruct((b, side, side, 3), jnp.float32,
                                            sharding=split),
             "labels": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=split)}

    sess = MonitorSession(mesh=mesh, name="ddp-resnet18")
    cap = sess.capture(step, params, params, batch)
    assert sess.topo.hw is V5E                    # from device_kind
    assert "all-reduce" in cap.hlo_text
    compiled = sum(op.payload_bytes * op.weight for op in cap.ops
                   if op.kind == "all-reduce")
    traced = sum(ev.payload_bytes for ev in cap.traced
                 if ev.primitive == "psum")
    assert compiled == traced == DDP_PSUM_BYTES
    assert cap.memory_stats["total_bytes"] > 0 and cap.cost["flops"] > 0


def test_resnet18_bn_ddp_collectives(topo):
    """The published ResNet-18 (BatchNorm) under DDP as the benchmark runs
    it, 64 64x64 images per chip at float32 HIGHEST: the TPU compiler emits
    the gradients' all-reduce, 45,116,452 B, and, for the buffer broadcast
    from chip 0, three asynchronous collective-permute-starts of the
    38,400-byte buffer along the chain 0 -> 1 -> 2 -> 3 (XLA:TPU has no
    collective-broadcast); compiled equal to traced."""
    from repro.core import MonitorSession
    from repro.models.resnet import ResNet18
    from repro.train import ddp

    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    model = ResNet18(200, published=True, precision="highest")
    step = ddp.make_ddp_train_step(model.stateful_loss_fn, mesh,
                                   bucket_mb=25.0, stateful=True)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = lambda t, s: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), t)
    params = shapes(model.shapes(), repl)
    state = shapes(jax.eval_shape(model.init_state), repl)
    batch = {"images": jax.ShapeDtypeStruct((256, 64, 64, 3), jnp.float32,
                                            sharding=split),
             "labels": jax.ShapeDtypeStruct((256,), jnp.int32,
                                            sharding=split)}
    sess = MonitorSession(mesh=mesh, name="ddp-resnet18-bn")
    cap = sess.capture(step, params, state, params, batch)
    assert "collective-permute-start" in cap.hlo_text
    assert "collective-broadcast" not in cap.hlo_text
    permutes = [op for op in cap.ops if op.kind == "collective-permute"]
    assert sorted(op.source_target_pairs[0] for op in permutes) == [
        (0, 1), (1, 2), (2, 3)]
    assert [op.payload_bytes for op in permutes] == [38_400] * 3
    compiled = sum(op.payload_bytes * op.weight for op in cap.ops
                   if op.kind == "all-reduce")
    traced = sum(ev.payload_bytes for ev in cap.traced
                 if ev.primitive == "psum")
    assert compiled == traced == DDP_BN_PSUM_BYTES
    assert sum(ev.payload_bytes for ev in cap.traced
               if ev.primitive == "ppermute") == 3 * 38_400


def test_served_decode_updates_the_cache_in_place(topo):
    """granite-3-2b's served decode at published widths (4 of its 40
    layers, a 32-request batch): the cache is donated and aliased, and the
    step holds no copy of it.  Its temporaries grow by less than two
    layers' K+V from a 64- to a 640-position cache; donating a decode that
    rebuilds the stack holds a whole second one (1.68 GB at 40 layers)."""
    import dataclasses

    from repro import configs
    from repro.core import MonitorSession
    from repro.models import build_model
    from repro.parallel import Sharder
    from repro.serve import ServeConfig, cache_shardings, make_serve_steps

    mesh = Mesh(np.array(topo.devices[:1]), ("data",),
                axis_types=(AxisType.Auto,))
    shd = Sharder(mesh)
    model = build_model(dataclasses.replace(configs.config("granite_3_2b"),
                                            n_layers=4))
    params_sh = shd.tree_shardings(model.shapes(), model.axes())

    def shapes(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    batch, stats, stack = 32, {}, {}
    for max_len in (64, 640):
        scfg = ServeConfig(max_len=max_len, batch=batch)
        _, decode = make_serve_steps(model, shd, scfg, params_sh)
        cache = shapes(model.cache_shapes(batch, max_len),
                       cache_shardings(model, scfg, shd))
        tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32,
                                      sharding=NamedSharding(mesh, P()))
        cap = MonitorSession(mesh=mesh, name="decode").capture(
            decode, shapes(model.shapes(), params_sh), cache,
            {"tokens": tokens})
        stats[max_len] = cap.memory_stats
        stack[max_len] = sum(a.size * a.dtype.itemsize
                             for a in jax.tree.leaves(cache))
        # the whole cache, the length scalar padded to its tile
        assert stats[max_len]["alias_bytes"] >= stack[max_len]
    layer_kv = (stack[640] - 4) // 4
    assert stats[640]["temp_bytes"] - stats[64]["temp_bytes"] < 2 * layer_kv
