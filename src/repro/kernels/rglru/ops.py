"""Dispatch wrapper for the RG-LRU recurrence.

TPU: single-pass Pallas kernel, chunked over the sequence so each tile fits
VMEM (state is carried between chunks through h0).  Elsewhere: XLA
``associative_scan`` (log-depth) — also the gradient path (the Pallas kernel
is forward-only; models call this op inside ``jax.checkpoint`` regions or
serving paths).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rglru_scan(x, log_a, h0=None, *, force: str = "", seq_chunk: int = 4096):
    """h_t = exp(log_a_t)*h_{t-1} + x_t over axis 1.  (B,S,D) -> (B,S,D)."""
    backend = force or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if backend in ("pallas", "pallas_interpret"):
        from .kernel import rglru_pallas
        b, s, d = x.shape
        interp = backend == "pallas_interpret"
        if s <= seq_chunk:
            return rglru_pallas(x, log_a, h0, interpret=interp)
        assert s % seq_chunk == 0
        outs = []
        h = h0
        for i in range(s // seq_chunk):
            sl = slice(i * seq_chunk, (i + 1) * seq_chunk)
            o = rglru_pallas(x[:, sl], log_a[:, sl], h, interpret=interp)
            h = o[:, -1]
            outs.append(o)
        return jnp.concatenate(outs, axis=1)
    if backend == "xla":
        def combine(c1, c2):
            la1, x1 = c1
            la2, x2 = c2
            return la1 + la2, jnp.exp(la2) * x1 + x2
        xx = x if h0 is None else x.at[:, 0].add(
            jnp.exp(log_a[:, 0]) * h0)
        _, h = jax.lax.associative_scan(combine, (log_a, xx), axis=1)
        return h
    from .ref import rglru_ref
    return rglru_ref(x, log_a, h0)
