"""Flash attention as a Pallas TPU kernel.

Blockwise online-softmax attention (Dao et al., adapted to the TPU memory
hierarchy): the (Sq, Skv) score matrix never leaves VMEM; HBM traffic is
O(S * dh) instead of O(S^2).

Grid: ``(B, H, nq, nk)`` — the trailing (kv) dimension is innermost and
sequential on TPU, so VMEM scratch accumulators (acc, m, l) carry the online
softmax across kv blocks of one (b, h, q-block) cell and are finalized on the
last kv step.

BlockSpecs (all VMEM):
  q:   (1, 1, Bq, dh)   indexed (b, h, qi)       — revisited across ki
  k,v: (1, 1, Bk, dh)   indexed (b, h // G, ki)  — GQA: query-head groups
                                                    share a kv head
  out: (1, 1, Bq, dh)   indexed (b, h, qi)

Causal/window masking is positional; blocks fully outside the mask are
skipped with ``pl.when`` (the MXU never sees them).  Block sizes default to
(128, 512): q/k/v tiles are MXU-aligned (128 lanes) and the working set
(q + k + v + acc + p) stays under ~4 MiB of VMEM for dh <= 256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, nk: int, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    q_start = q_offset + qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # --- block-level mask culling -------------------------------------
    # causal: skip blocks strictly above the diagonal
    # window: skip blocks entirely older than (q_start - window)
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window > 0:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (Bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)                  # (Bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                   # (Bq,)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # fully masked rows: keep contributions at exactly zero
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)                  # (Bk, dh)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0, ...] = (
            acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k",
                     "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 512,
                    q_offset: int = 0, interpret: bool = False):
    """q: (B,Sq,H,dh); k,v: (B,Skv,KVH,dh) -> (B,Sq,H,dh).

    Differentiable: the backward pass is the VJP of the chunked XLA path
    (:func:`repro.models.attention.chunked_attention`, the kernel's oracle),
    recomputed from q, k and v -- the kernel itself is forward-only.
    """
    block_q = min(block_q, q.shape[1])
    block_k = min(block_k, k.shape[1])
    return _flash_attention(q, k, v, causal, window, block_q, block_k,
                            q_offset, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, causal, window, block_q, block_k, q_offset,
                     interpret):
    return _pallas_forward(q, k, v, causal, window, block_q, block_k,
                           q_offset, interpret)


def _flash_attention_fwd(q, k, v, causal, window, block_q, block_k,
                         q_offset, interpret):
    out = _pallas_forward(q, k, v, causal, window, block_q, block_k,
                          q_offset, interpret)
    return out, (q, k, v)


def _flash_attention_bwd(causal, window, block_q, block_k, q_offset,
                         interpret, residuals, g):
    from repro.models.attention import chunked_attention
    _, vjp = jax.vjp(functools.partial(
        chunked_attention, causal=causal, window=window, q_chunk=block_q,
        q_offset=q_offset), *residuals)
    return vjp(g)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _pallas_forward(q, k, v, causal, window, block_q, block_k, q_offset,
                    interpret):
    b, sq, h, dh = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    if sq % block_q or skv % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"the sequence lengths ({sq}, {skv})")
    nq, nk = sq // block_q, skv // block_k
    scale = dh ** -0.5

    # layout: (B, H, S, dh) blocks
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),      # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),      # l (running denom)
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.swapaxes(1, 2)


def vmem_bytes(block_q: int, block_k: int, dh: int, dtype_bytes: int = 2) -> int:
    """Working-set estimate for one grid cell (used to pick block sizes)."""
    q = block_q * dh * dtype_bytes
    kv = 2 * block_k * dh * dtype_bytes
    s_p = 2 * block_q * block_k * 4
    acc = block_q * dh * 4 + 2 * block_q * 4
    return q + kv + s_p + acc
