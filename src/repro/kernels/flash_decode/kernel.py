"""Flash-decode: single-token attention over a deep KV cache, as Pallas.

Decode is the memory-bound regime (roofline table: every ``decode_32k`` cell)
— the step reads the whole cache once and does ~2 FLOPs/byte.  The win over
the XLA path is eliminating the fp32 materializations around the score
vector: the cache streams through VMEM in (block_k x dh) tiles, the online
softmax lives in VREG-resident scratch, and HBM traffic is exactly
``k + v + q + out`` bytes.

Grid: ``(B, H, L // block_k)`` — kv-block innermost (sequential on TPU), so
scratch (acc, m, l) carries the online softmax and is finalized on the last
block.  GQA maps query head h to cache head ``h // G`` in the BlockSpec
index_map.  ``cache_len`` arrives as a scalar-prefetch operand; blocks
entirely past it are skipped (``pl.when``), so a short cache in a long
buffer costs only the occupied blocks' bandwidth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, block_k: int, nk: int, scale: float,
                   window: int):
    ki = pl.program_id(2)
    cache_len = len_ref[0]
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = k_start < cache_len
    if window > 0:
        run = jnp.logical_and(run, k_start + block_k > cache_len - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (dh,)
        k = k_ref[0, 0].astype(jnp.float32)                  # (Bk, dh)
        s = jnp.sum(q[None, :] * k, axis=-1)                 # (Bk,)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)
        mask = kpos < cache_len
        if window > 0:
            mask &= kpos >= cache_len - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # (Bk,)
        v = v_ref[0, 0].astype(jnp.float32)                  # (Bk, dh)
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(
            p[:, None] * v, axis=0)
        l_ref[0] = l_ref[0] * alpha + p.sum()
        m_ref[0] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0, ...] = (acc_ref[...] /
                            jnp.maximum(l_ref[0], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_k", "interpret"))
def flash_decode(q, k_cache, v_cache, cache_len, *, window: int = 0,
                 block_k: int = 512, interpret: bool = False):
    """q: (B,H,dh); k/v: (B,L,KVH,dh); cache_len: () int32 -> (B,H,dh)."""
    b, h, dh = q.shape
    _, lmax, kvh, _ = k_cache.shape
    g = h // kvh
    block_k = min(block_k, lmax)
    assert lmax % block_k == 0, (lmax, block_k)
    nk = lmax // block_k

    kt = k_cache.swapaxes(1, 2)                              # (B,KVH,L,dh)
    vt = v_cache.swapaxes(1, 2)
    qt = q[:, :, None, :]                                    # (B,H,1,dh)
    cache_len = jnp.asarray(cache_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, block_k=block_k, nk=nk,
                               scale=dh ** -0.5, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h_, ki: (0,)),
            pl.BlockSpec((1, 1, dh), lambda b_, h_, ki: (b_, h_, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, ki: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, ki: (b_, h_ // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dh), lambda b_, h_, ki: (b_, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh,), jnp.float32),     # acc
            pltpu.VMEM((1,), jnp.float32),      # m
            pltpu.VMEM((1,), jnp.float32),      # l
        ],
        interpret=interpret,
    )(cache_len, qt.reshape(b, h, dh), kt, vt)
    return out

