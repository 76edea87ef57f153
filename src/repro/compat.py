"""Process-level jax setup shared by the CLI, the launchers and the scripts:
forced host-device counts for CPU modelling, Auto-typed meshes, and the
persistent compilation cache.
"""
from __future__ import annotations

import os

import jax

# the checkout root: src/repro/compat.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECKOUT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_host_devices(n: int):
    """Append ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``.

    Appends rather than overwrites so user-set flags survive; an existing
    device-count flag (user-chosen) wins.  Must run before the jax backend
    initializes (first device query).  Only the CPU backend reads it: on a
    host with an accelerator the default backend keeps its own devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    lives at the fixed ``<checkout>/.jax_cache``.  The path is part of what
    a later run must find again, so it is never a temporary or per-process
    directory.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types (jax defaults to Explicit)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))
