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


@pytest.mark.parametrize("seq", [512, 1000])   # 1000: whole-sequence blocks
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
