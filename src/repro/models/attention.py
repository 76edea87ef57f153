"""Attention: GQA/MQA/MHA with RoPE, optional qk-norm, causal or sliding
window, chunked (flash-style) training path and KV-cache decode path.

The chunked path is the pure-JAX oracle of ``repro.kernels.flash_attention``;
the distributed models call :func:`repro.kernels.flash_attention.ops.attend`
which dispatches to the Pallas kernel on TPU and to this path elsewhere.

Sharding policy (computed from the mesh, see DESIGN.md §5): shard heads over
the model axis when divisible, else fall back to head_dim, else replicate.
The KV cache's sequence dim is sharded over the model axis for decode
(context parallelism) — that is what fits a 32k x 128-batch cache in HBM.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import ModelConfig, Spec, rms_norm
from .layers import apply_rope

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------
def attn_spec(cfg: ModelConfig, stacked: int = 0,
              n_heads: Optional[int] = None,
              n_kv_heads: Optional[int] = None) -> dict:
    d, dh = cfg.d_model, cfg.dh
    nh = n_heads or cfg.n_heads
    nkv = n_kv_heads or cfg.n_kv_heads
    lead = (stacked,) if stacked else ()
    lx = ("layers",) if stacked else ()
    spec = {
        "wq": Spec(lead + (d, nh * dh), lx + ("embed", "heads")),
        "wk": Spec(lead + (d, nkv * dh), lx + ("embed", "kv_heads")),
        "wv": Spec(lead + (d, nkv * dh), lx + ("embed", "kv_heads")),
        "wo": Spec(lead + (nh * dh, d), lx + ("heads", "embed")),
    }
    if cfg.qk_norm:
        spec["q_norm"] = Spec(lead + (dh,), lx + (None,), init="ones")
        spec["k_norm"] = Spec(lead + (dh,), lx + (None,), init="ones")
    return spec


def head_sharding_axes(cfg: ModelConfig, shd, nh: int, nkv: int):
    """(q_axes, kv_axes).  Training/prefill always use head sharding: when
    heads % tp != 0 the attention path zero-pads heads up to the next
    multiple of tp (llama4: 40 -> 48, +20% attention FLOPs) — measured to
    beat both alternatives:

    * head_dim sharding: contracting a sharded dh emits a score-matrix
      all-reduce per q-chunk per layer (llama4 train_4k: 2.7 PiB/step);
    * context-parallel (seq-sharded q): forces single-block scores,
      21 GiB/layer transient at 32k prefill (llama4: 64 GiB/dev peak).

    (EXPERIMENTS.md §Perf llama4 iterations 1 and 5.)
    """
    tp = shd.logical_size("heads")
    if tp > 1:
        q_ax = ("batch", "seq", "heads", None)
        kv_ax = ("batch", "seq",
                 "kv_heads" if nkv % tp == 0 else None, None)
    else:
        q_ax = ("batch", "seq", None, None)
        kv_ax = q_ax
    return q_ax, kv_ax


def pad_heads(x, nh_pad: int):
    """Zero-pad the head dim (axis 2) up to nh_pad."""
    b, s, nh, dh = x.shape
    if nh == nh_pad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((b, s, nh_pad - nh, dh), x.dtype)], axis=2)


# ---------------------------------------------------------------------------
# core attention math (chunked, flash-style oracle)
#
# GQA is evaluated in repeat-KV MHA form: k/v are broadcast to the full head
# count BEFORE the einsums so every tensor keeps a single fused head dim.
# The grouped 5-D form (B,S,KVH,G,dh) shards KVH x G across the model axis
# only when both factors divide it — when they don't (granite: 8x4 over 16),
# GSPMD falls back to "involuntary full rematerialization" and emits a
# full all-gather of the score tensor per chunk (measured: 2.4 PB/step on
# granite-3-2b prefill_32k; EXPERIMENTS.md §Perf iteration 1).
# ---------------------------------------------------------------------------
def _expand_kv(k, h: int):
    """(B,S,KVH,dh) -> (B,S,H,dh) by broadcasting each kv head over its
    query group (free at the XLA level: a broadcast, not a copy)."""
    b, s, kvh, dh = k.shape
    if kvh == h:
        return k
    g = h // kvh
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, kvh, g, dh)).reshape(b, s, h, dh)


def _attend_block(qc, k, v, qpos, kpos, *, causal: bool, window: int):
    """qc: (B,cq,H,dh); k,v: (B,Skv,H,dh) (kv pre-expanded); global pos."""
    scale = qc.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bshd->bhqs",
                   (qc * scale).astype(jnp.float32), k.astype(jnp.float32))
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p.astype(v.dtype), v)


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      q_chunk: int = 512, q_offset: int = 0):
    """Flash-style attention that never materializes (Sq,Skv) for all heads.

    q: (B,Sq,H,dh); k,v: (B,Skv,KVH,dh).  ``q_offset`` is the global position
    of q[0] (prefill continuation).  Returns (B,Sq,H,dh).
    """
    b, sq, h, dh = q.shape
    _, skv, kvh, _ = k.shape
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    kpos_full = jnp.arange(skv)
    if q_chunk >= sq:
        qpos = q_offset + jnp.arange(sq)
        return _attend_block(q, k, v, qpos, kpos_full, causal=causal,
                             window=window if window > 0 else 0)

    n_chunks = sq // q_chunk
    assert sq % q_chunk == 0, (sq, q_chunk)
    qs = q.reshape(b, n_chunks, q_chunk, h, dh).swapaxes(0, 1)

    use_slice = window > 0 and skv > window + q_chunk

    def body(_, xs):
        qc, idx = xs
        qpos = q_offset + idx * q_chunk + jnp.arange(q_chunk)
        if use_slice:
            slice_len = window + q_chunk
            start = jnp.clip(q_offset + (idx + 1) * q_chunk - slice_len,
                             0, skv - slice_len)
            kc = jax.lax.dynamic_slice_in_dim(k, start, slice_len, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, slice_len, axis=1)
            kpos = start + jnp.arange(slice_len)
        else:
            kc, vc, kpos = k, v, kpos_full
        out = _attend_block(qc, kc, vc, qpos, kpos, causal=causal,
                            window=window)
        return None, out

    _, outs = jax.lax.scan(body, None,
                           (qs, jnp.arange(n_chunks)))
    return outs.swapaxes(0, 1).reshape(b, sq, h, dh)


def decode_attention(q, k_cache, v_cache, k_new, v_new, cache_len, *,
                     window: int = 0, ring: bool = False):
    """Single-position decode: q (B,1,H,dh) over a cache of rows
    (B,L,KVH*dh), one row per position holding every kv head's lanes, and
    over the new token's own k_new, v_new (B,1,KVH,dh).

    ``cache_len`` (scalar int) is the number of valid positions, the new
    token's included; its slot (``(cache_len-1) % L`` if ``ring``) is left
    out of the cache, so a step attends before it writes its row.  A ring
    cache keeps only the last ``L`` (== window) positions — this is what
    bounds long_500k decode memory for windowed-attention archs.

    Each query head meets its kv head's lanes of a row through the query
    spread block-diagonally over the row: the cache is read as it lies,
    for ``KVH`` times the multiply-adds, where a decode step is bound by
    the bytes it reads.
    """
    b, _, h, dh = q.shape
    _, lmax, width = k_cache.shape
    kvh = width // dh
    g = h // kvh
    scale = dh ** -0.5
    eye = jnp.eye(kvh, dtype=k_cache.dtype)[:, None, :, None]
    qg = (q.reshape(b, kvh, g, dh) * scale).astype(k_cache.dtype)
    q_rows = (qg[:, :, :, None, :] * eye).reshape(b, h, width)
    s = jnp.einsum("bhc,bsc->bhs", q_rows, k_cache,
                   preferred_element_type=jnp.float32).reshape(b, kvh, g, lmax)
    kpos = jnp.arange(lmax)
    if ring:
        # slot i holds absolute position cache_len-1-age, age=(cache_len-1-i)%L
        age = jnp.mod(cache_len - 1 - kpos, lmax)
        mask = (age > 0) & (age < cache_len)  # written before this step
        if window > 0:
            mask &= age < window
    else:
        mask = kpos < cache_len - 1
        if window > 0:
            mask &= kpos >= cache_len - window
    s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    # softmax over the cache's positions and the new one
    k_new, v_new = (a.reshape(b, kvh, dh).astype(jnp.float32)
                    for a in (k_new, v_new))
    s_new = jnp.einsum("bkgd,bkd->bkg", qg.astype(jnp.float32), k_new)
    m = jnp.maximum(s.max(axis=-1), s_new)
    e, e_new = jnp.exp(s - m[..., None]), jnp.exp(s_new - m)
    total = e.sum(axis=-1) + e_new
    p = (e / total[..., None]).reshape(b, h, lmax).astype(v_cache.dtype)
    out = jnp.einsum("bhs,bsc->bhc", p, v_cache,
                     preferred_element_type=jnp.float32)
    out = (out.reshape(b, kvh, g, kvh, dh) * eye).sum(axis=3)
    out = out + (e_new / total)[..., None] * v_new[:, :, None]
    return out.astype(v_cache.dtype).reshape(b, 1, h, dh)


# ---------------------------------------------------------------------------
# full attention block (projections + rope + attend + out-proj)
# ---------------------------------------------------------------------------
def attention_block(params, x, cfg: ModelConfig, shd, *,
                    positions=None, cache=None, window: Optional[int] = None,
                    n_heads: Optional[int] = None,
                    n_kv_heads: Optional[int] = None):
    """Returns (out, new_cache).  ``cache=None`` -> training/prefill w/o cache.

    Prefill (``s > 1``): cache = {"k": (B,L,KVH*dh), "v": ...} gives the
    cache's shape; new_cache holds the sequence's tail and its "len".

    Decode (``s == 1``): cache = {"k": (n,B,L,KVH*dh), "v": ..., "len":
    int32 scalar, "layer": int32 scalar} holds every layer's rows; the
    step attends over layer ``layer``'s rows as they stand and its own k/v,
    and new_cache = {"k", "v"} is the stack with its row written at "len"
    (the ring slot for a window's ring buffer): the one write the step
    makes to this layer's cache, in place where the stack is carried
    through the layer scan and donated.
    """
    from repro.kernels.flash_attention import ops as flash_ops

    b, s, d = x.shape
    nh = n_heads or cfg.n_heads
    nkv = n_kv_heads or cfg.n_kv_heads
    dh = cfg.dh
    win = cfg.attn_window if window is None else window
    dt = x.dtype
    q_ax, kv_ax = head_sharding_axes(cfg, shd, nh, nkv)

    q = jnp.einsum("bsd,dk->bsk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dk->bsk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dk->bsk", x, params["wv"].astype(dt))
    q = q.reshape(b, s, nh, dh)
    k = k.reshape(b, s, nkv, dh)
    v = v.reshape(b, s, nkv, dh)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    if cache is None or s > 1:
        # training, or prefill (cache is filled with the sequence tail)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        k_gqa, v_gqa = k, v              # unpadded GQA form for the cache
        # expand GQA kv to full heads BEFORE the sharding constraint so kv
        # activations shard over the model axis like q (a replicated kv
        # forces per-layer all-gathers; §Perf iteration 2).
        #
        # heads % tp != 0 has two viable schedules (§Perf llama4 it. 5-6):
        #   context-parallel (seq-sharded q, single score block) — cheapest
        #     when the per-device score block fits comfortably;
        #   head padding to the next multiple of tp — bounded-memory chunked
        #     flash path, +pad/nh attention FLOPs (llama4 32k: 21 GiB/layer
        #     scores make cp unusable).
        tp = shd.logical_size("heads")
        use_cp = False
        if tp > 1 and nh % tp != 0:
            b_loc = max(1, b // max(1, shd.dp))
            cp_score_bytes = b_loc * nh * (s // tp) * s * 4
            use_cp = cp_score_bytes < (2 << 30)
        if use_cp:
            q = shd.constraint(q, ("batch", "attn_seq", None, None))
            k = shd.constraint(_expand_kv(k, nh), ("batch", None, None, None))
            v = shd.constraint(_expand_kv(v, nh), ("batch", None, None, None))
            out = flash_ops.attend(q, k, v, causal=True, window=win,
                                   q_chunk=s)
            out = shd.constraint(out, ("batch", "attn_seq", None, None))
        else:
            nh_pad = -(-nh // tp) * tp if tp > 1 else nh
            q = shd.constraint(pad_heads(q, nh_pad), q_ax)
            k = shd.constraint(pad_heads(_expand_kv(k, nh), nh_pad), q_ax)
            v = shd.constraint(pad_heads(_expand_kv(v, nh), nh_pad), q_ax)
            out = flash_ops.attend(q, k, v, causal=True, window=win)
            out = shd.constraint(out, q_ax)[:, :, :nh]
        new_cache = None
        if cache is not None:
            lmax = cache["k"].shape[1]
            kc = k_gqa.reshape(b, s, nkv * dh).astype(cache["k"].dtype)
            vc = v_gqa.reshape(b, s, nkv * dh).astype(cache["v"].dtype)
            if s >= lmax:            # ring layout: slot j holds pos p, p%lmax==j
                kc, vc = kc[:, -lmax:], vc[:, -lmax:]
                kc = jnp.roll(kc, s % lmax, axis=1)
                vc = jnp.roll(vc, s % lmax, axis=1)
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, 0, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, 0, axis=1)
            new_cache = {"k": kc, "v": vc,
                         "len": jnp.full((), s, jnp.int32)}
    else:
        pos, layer = cache["len"], cache["layer"]             # scalar int32
        lmax = cache["k"].shape[2]
        ring = win > 0 and lmax <= win                        # ring buffer
        positions = jnp.broadcast_to(pos[None, None], (b, 1))
        q = shd.constraint(apply_rope(q, positions, cfg.rope_theta), q_ax)
        k = shd.constraint(apply_rope(k, positions, cfg.rope_theta), kv_ax)
        v = shd.constraint(v, kv_ax)
        k = k.astype(cache["k"].dtype)
        v = v.astype(cache["v"].dtype)
        rows = [jax.lax.dynamic_index_in_dim(cache[name], layer,
                                             keepdims=False,
                                             allow_negative_indices=False)
                for name in ("k", "v")]
        out = decode_attention(q, *rows, k, v, pos + 1, window=win,
                               ring=ring)
        out = shd.constraint(out, q_ax)
        slot = jnp.mod(pos, lmax) if ring else pos
        new_cache = {name: jax.lax.dynamic_update_slice(
                         cache[name], new.reshape(1, b, 1, nkv * dh),
                         (layer, 0, slot, 0), allow_negative_indices=False)
                     for name, new in (("k", k), ("v", v))}

    out = jnp.einsum("bsk,kd->bsd",
                     out.reshape(b, -1, nh * dh).astype(dt),
                     params["wo"].astype(dt))
    return shd.constraint(out, ("batch", "seq", None)), new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                  n_kv_heads: Optional[int] = None, dtype: str = "bfloat16",
                  window: Optional[int] = None):
    """A zero cache of one row of KVH*dh values per position.  A decode
    step writes one row; a TPU lays a (B,L,KVH,dh) cache with positions
    minor instead (dh 64 fills half a 128-lane tile), where writing one
    position is a masked store into every tile of the layer."""
    nkv = n_kv_heads or cfg.n_kv_heads
    win = cfg.attn_window if window is None else window
    if win > 0:
        max_len = min(max_len, win)                           # ring buffer
    shape = (batch, max_len, nkv * cfg.dh)
    return {
        "k": jnp.zeros(shape, jnp.dtype(dtype)),
        "v": jnp.zeros(shape, jnp.dtype(dtype)),
        "len": jnp.zeros((), jnp.int32),
    }
