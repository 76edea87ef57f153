"""Dispatch wrapper for attention: Pallas kernel on TPU, chunked-XLA oracle
elsewhere (CPU dry-runs / smoke tests).

``attend`` is the call-site used by every transformer model in the framework;
the choice of backend never changes numerics beyond dtype-accumulation noise
(asserted in tests/test_kernels.py).
"""
from __future__ import annotations

import jax


def attend(q, k, v, *, causal: bool = True, window: int = 0,
           q_chunk: int = 512, q_offset: int = 0, force: str = ""):
    """q: (B,Sq,H,dh); k,v: (B,Skv,KVH,dh) -> (B,Sq,H,dh).

    ``force``: "" (auto) | "pallas" | "pallas_interpret" | "xla" | "ref".
    """
    backend = force or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if backend in ("pallas", "pallas_interpret"):
        from .kernel import flash_attention    # blocks chosen from the shapes
        return flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            interpret=(backend == "pallas_interpret"))
    if backend == "xla":
        from repro.models.attention import chunked_attention
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_chunk=q_chunk, q_offset=q_offset)
    from .ref import attention_ref
    return attention_ref(q, k, v, causal=causal, window=window,
                         q_offset=q_offset)
