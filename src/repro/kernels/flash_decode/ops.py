"""Dispatch wrapper for decode attention: Pallas flash-decode on TPU,
grouped-einsum XLA path elsewhere (what the CPU dry-run lowers)."""
from __future__ import annotations

import jax


def decode_attend(q, k_cache, v_cache, cache_len, *, window: int = 0,
                  force: str = ""):
    """q: (B,H,dh); k/v: (B,L,KVH,dh) -> (B,H,dh)."""
    backend = force or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if backend in ("pallas", "pallas_interpret"):
        from .kernel import flash_decode
        lmax = k_cache.shape[1]
        bk = 512 if lmax % 512 == 0 else (128 if lmax % 128 == 0 else lmax)
        return flash_decode(q, k_cache, v_cache, cache_len, window=window,
                            block_k=bk,
                            interpret=(backend == "pallas_interpret"))
    from .ref import decode_ref
    return decode_ref(q, k_cache, v_cache, cache_len, window=window)
