"""Dispatch wrapper for RMSNorm."""
from __future__ import annotations

import jax


def rmsnorm(x, w, eps: float = 1e-6, *, force: str = ""):
    backend = force or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if backend in ("pallas", "pallas_interpret"):
        from .kernel import rmsnorm_pallas
        return rmsnorm_pallas(x, w, eps=eps,
                              interpret=(backend == "pallas_interpret"))
    from .ref import rmsnorm_ref
    return rmsnorm_ref(x, w, eps)
