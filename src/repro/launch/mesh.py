"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).

Single pod : (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips; "pod" crosses DCN
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    import numpy as np
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — the dry-run launcher must "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    # slice explicitly: a 512-device process also builds the 256-chip mesh
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(devs[:n]).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(4, 2), axes=("data", "model")):
    """Small mesh for tests/examples on forced host devices."""
    from repro.compat import make_mesh
    return make_mesh(shape, axes)


def mesh_name(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape) + ":" + \
        ",".join(map(str, mesh.axis_names))
