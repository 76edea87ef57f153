"""Explicit DDP gradient synchronization — the paper's PyTorch scenario.

PyTorch-DDP issues one ncclAllReduce per gradient bucket (Table 3 of the
paper; gradient bucketing is [16] Li et al.).  This module reproduces that
communication pattern with *application-issued* collectives (``jax.lax.psum``
inside ``shard_map``) in three flavours the benchmarks sweep:

* ``per_param`` — one AllReduce per gradient tensor (naive DDP),
* ``bucketed``  — gradients flattened/concatenated into ~``bucket_mb`` MiB
  buckets, one AllReduce per bucket (PyTorch default, 25 MiB),
* optional bf16 compression with fp32 error-feedback on either.

Because these collectives are traced by the application, the interceptor
(LD_PRELOAD analogue) sees them — this is the path that exercises the
paper's original workflow end-to-end.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------
def bucket_plan(params, bucket_mb: float = 25.0):
    """Greedy assignment of leaves to ~bucket_mb MiB buckets (by fp32 size)."""
    leaves, treedef = jax.tree.flatten(params)
    limit = bucket_mb * 1024 * 1024
    buckets, cur, cur_bytes = [], [], 0.0
    for i, leaf in enumerate(leaves):
        nbytes = float(np.prod(leaf.shape)) * 4
        if cur and cur_bytes + nbytes > limit:
            buckets.append(cur)
            cur, cur_bytes = [], 0.0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets, treedef


def allreduce_bucketed(grads, axis_name: str, bucket_mb: float = 25.0,
                       compress: bool = False, error_feedback=None):
    """AllReduce grads in buckets.  Returns (synced grads, new error_feedback).

    ``compress=True`` casts each bucket to bf16 for the wire (half bytes) and
    keeps the fp32 quantization error in ``error_feedback`` (same structure
    as grads) to be re-added next step — classic EF compression.
    """
    leaves, treedef = jax.tree.flatten(grads)
    ef_leaves = (treedef.flatten_up_to(error_feedback)
                 if error_feedback is not None else [None] * len(leaves))
    buckets, _ = bucket_plan(grads, bucket_mb)
    out = [None] * len(leaves)
    new_ef = [None] * len(leaves)
    for idx in buckets:
        flat = []
        for i in idx:
            g = leaves[i].astype(jnp.float32)
            if ef_leaves[i] is not None:
                g = g + ef_leaves[i]
            flat.append(g.reshape(-1))
        buf = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
        if compress:
            wire = buf.astype(jnp.bfloat16)
            err = buf - wire.astype(jnp.float32)
            buf = jax.lax.pmean(wire, axis_name).astype(jnp.float32)
        else:
            err = None
            buf = jax.lax.pmean(buf, axis_name)
        off = 0
        for i in idx:
            n = int(np.prod(leaves[i].shape))
            out[i] = buf[off:off + n].reshape(leaves[i].shape)
            if err is not None:
                new_ef[i] = err[off:off + n].reshape(leaves[i].shape)
            off += n
    grads_out = jax.tree.unflatten(treedef, out)
    ef_out = (jax.tree.unflatten(treedef, new_ef)
              if compress and error_feedback is not None else error_feedback)
    return grads_out, ef_out


def allreduce_per_param(grads, axis_name: str):
    """One AllReduce per tensor (naive DDP; paper's D x N counting)."""
    return jax.tree.map(lambda g: jax.lax.pmean(g, axis_name), grads)


# ---------------------------------------------------------------------------
# a complete DDP train step (shard_map over the data axis)
# ---------------------------------------------------------------------------
def make_ddp_train_step(loss_fn: Callable, mesh, *, axis_name: str = "data",
                        mode: str = "bucketed", bucket_mb: float = 25.0,
                        compress: bool = False, lr: float = 1e-3):
    """loss_fn(params, batch) -> (loss, metrics).  Params replicated; batch
    sharded over ``axis_name``.  SGD update inline (the paper's apps)."""

    def step(params, ef, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        if mode == "per_param":
            grads = allreduce_per_param(grads, axis_name)
        else:
            grads, ef = allreduce_bucketed(grads, axis_name, bucket_mb,
                                           compress=compress,
                                           error_feedback=ef)
        loss = jax.lax.pmean(loss, axis_name)
        new_params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new_params, ef, loss

    in_specs = (P(), P(), P(axis_name))
    out_specs = (P(), P(), P())
    mapped = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(mapped)


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
