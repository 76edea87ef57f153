"""Flash attention as a Pallas TPU kernel.

Blockwise online-softmax attention (Dao et al., adapted to the TPU memory
hierarchy): the (Sq, Skv) score matrix never leaves VMEM; HBM traffic is
O(S * dh) instead of O(S^2).

Grid: ``(B, H, steps)``.  The steps are the (q block, kv block) pairs in
which the causal/window mask leaves any key, q-major, listed at trace time
and handed to the kernel as scalar-prefetch arrays (``_block_pairs``).  A
block wholly outside the mask takes no grid step and no DMA.  The step axis
is sequential on TPU, so VMEM scratch accumulators (acc, m, l) carry the
online softmax across the kv blocks of one q block: they are reset on its
first pair and the output is written on its last.

BlockSpecs (all VMEM):
  q:   (1, 1, Bq, dh)   indexed (b, h, qi[step])       — revisited across ki
  k,v: (1, 1, Bk, dh)   indexed (b, h // G, ki[step])  — GQA: query-head
                                                         groups share a kv head
  out: (1, 1, Bq, dh)   indexed (b, h, qi[step])

Both matmuls take their operands in the input dtype and accumulate in
float32: bf16 inputs make one MXU pass each, float32 inputs stay float32.
The softmax scale goes on the float32 scores; p is cast to v's dtype for
p.v, as the XLA path does.  Only pairs that straddle the diagonal or the
window's edge build the element mask.  The running max and sum are
lane-replicated (Bq, 128) float32 tiles, as in the reference TPU kernel,
so no step relays them out.

Block sizes (``choose_blocks``, when not given): the largest of 512, 256
and 128 that divides each length and keeps ``vmem_bytes`` under
``VMEM_BUDGET``; a length none divides is one whole-sequence block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128
BLOCKS = (512, 256, 128)
VMEM_BUDGET = 8 << 20          # leaves room under the default scoped VMEM
FIRST, LAST, MASKED = 1, 2, 4  # a step's flags in ``_block_pairs``
NT = (((1,), (1,)), ((), ()))  # q @ k^T


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) tile as (rows, n)."""
    reps, rem = divmod(n, LANES)
    if rem == 0:
        return jnp.tile(x, (1, reps)) if reps > 1 else x
    if reps == 0:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _flash_kernel(qblk_ref, kblk_ref, flags_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                  window: int, block_q: int, block_k: int, q_offset: int):
    step = pl.program_id(2)
    flags = flags_ref[step]

    @pl.when((flags & FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked: bool):
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0], NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            q_start = q_offset + qblk_ref[step] * block_q
            k_start = kblk_ref[step] * block_k
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                   # (Bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, block_k))
        if masked:
            # rows with no key yet keep contributions at exactly zero
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]                                       # (Bk, dh)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, acc_ref.shape[1]) + pv
        m_ref[...] = m_new

    masked = (flags & MASKED) != 0
    pl.when(masked)(lambda: update(True))
    pl.when(jnp.logical_not(masked))(lambda: update(False))

    @pl.when((flags & LAST) != 0)
    def _finalize():
        l = _lanes(jnp.maximum(l_ref[...], 1e-30), acc_ref.shape[1])
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _block_pairs(nq: int, nk: int, block_q: int, block_k: int, causal: bool,
                 window: int, q_offset: int) -> np.ndarray:
    """(3, steps) int32: the q block, kv block and flags of every pair in
    which the mask leaves any key, q-major.  MASKED marks a pair that
    straddles the diagonal or the window's edge; FIRST and LAST a q block's
    first and last pair.  A q block the mask leaves no key keeps one masked
    pair, so its rows are written (as zeros)."""
    steps = []
    for qi in range(nq):
        q_lo = q_offset + qi * block_q
        q_hi = q_lo + block_q - 1
        row = []
        for ki in range(nk):
            k_lo = ki * block_k
            k_hi = k_lo + block_k - 1
            if causal and k_lo > q_hi:
                continue                      # wholly above the diagonal
            if window > 0 and k_hi <= q_lo - window:
                continue                      # wholly older than the window
            edge = ((causal and k_hi > q_lo)
                    or (window > 0 and k_lo <= q_hi - window))
            row.append([qi, ki, MASKED if edge else 0])
        row = row or [[qi, 0, MASKED]]
        row[0][2] |= FIRST
        row[-1][2] |= LAST
        steps += row
    return np.asarray(steps, np.int32).T


def vmem_bytes(block_q: int, block_k: int, dh: int, dtype_bytes: int = 2) -> int:
    """Working-set estimate for one grid step (used to pick block sizes):
    the double-buffered q, k, v and out tiles, the scores and p in float32
    and p in the input dtype, acc and the lane-replicated m and l."""
    tiles = 2 * (2 * block_q + 2 * block_k) * dh * dtype_bytes
    scores = block_q * block_k * (2 * 4 + dtype_bytes)
    stats = block_q * (dh + 2 * LANES) * 4
    return tiles + scores + stats


def choose_blocks(sq: int, skv: int, dh: int, dtype) -> tuple[int, int]:
    """(block_q, block_k) for these lengths: the largest of ``BLOCKS`` that
    divides each and fits ``VMEM_BUDGET``, q first; a length none divides
    is one whole-sequence block."""
    size = jnp.dtype(dtype).itemsize
    qs = [b for b in BLOCKS if sq % b == 0] or [sq]
    ks = [b for b in BLOCKS if skv % b == 0] or [skv]
    for bq in qs:
        for bk in ks:
            if vmem_bytes(bq, bk, dh, size) <= VMEM_BUDGET:
                return bq, bk
    return qs[-1], ks[-1]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k",
                     "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int | None = None, block_k: int | None = None,
                    q_offset: int = 0, interpret: bool = False):
    """q: (B,Sq,H,dh); k,v: (B,Skv,KVH,dh) -> (B,Sq,H,dh).

    ``block_q``/``block_k`` left out are chosen from the shapes
    (``choose_blocks``).  Differentiable: the backward pass is the VJP of
    the chunked XLA path (:func:`repro.models.attention.chunked_attention`,
    the kernel's oracle), recomputed from q, k and v -- the kernel itself
    is forward-only.
    """
    bq, bk = choose_blocks(q.shape[1], k.shape[1], q.shape[3], q.dtype)
    block_q = min(block_q or bq, q.shape[1])
    block_k = min(block_k or bk, k.shape[1])
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
    # q chunks of at most 128 rows whatever the forward's blocks: each
    # chunk materialises (B, chunk, H, Skv) float32 scores
    sq = residuals[0].shape[1]
    q_chunk = min(block_q, 128)
    if sq % q_chunk:
        q_chunk = block_q
    _, vjp = jax.vjp(functools.partial(
        chunked_attention, causal=causal, window=window, q_chunk=q_chunk,
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
    pairs = _block_pairs(sq // block_q, skv // block_k, block_q, block_k,
                         causal, window, q_offset)

    # layout: (B, H, S, dh) blocks
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)

    kernel = functools.partial(
        _flash_kernel, scale=dh ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, q_offset=q_offset)

    def q_map(b_, h_, s, qblk, kblk, flags):
        return b_, h_, qblk[s], 0

    def kv_map(b_, h_, s, qblk, kblk, flags):
        return b_, h_ // g, kblk[s], 0

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, pairs.shape[1]),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, dh), q_map),
                pl.BlockSpec((1, 1, block_k, dh), kv_map),
                pl.BlockSpec((1, 1, block_k, dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, dh), jnp.float32),      # acc
                pltpu.VMEM((block_q, LANES), jnp.float32),   # m (running max)
                pltpu.VMEM((block_q, LANES), jnp.float32),   # l (running sum)
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*map(jnp.asarray, pairs), qt, kt, vt)
    return out.swapaxes(1, 2)
