"""Keys and draws from ``--seed``, shared by the weights, the feed and the
plain references, so that a reference can make again, leaf by leaf or
layer by layer, exactly what the benchmark handed the program.

A seed may exceed 32 bits; it is split into the two words of a
threefry key, so every whole number from 0 to 2**64 - 1 gives its own key.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

_M32 = 0xFFFFFFFF


def root_key(seed: int):
    """The raw threefry key of ``seed`` (any whole number; negatives wrap)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([(s >> 32) & _M32, s & _M32], np.uint32))


def leaf_key(root, name: str, index=None):
    """The key of one named draw under ``root`` (from :func:`root_key`),
    and of its ``index``-th slice (a layer, a batch) where the draw is
    stacked.  ``root`` and ``index`` may be traced: a jitted maker that
    takes the root as an argument compiles once for every seed."""
    key = jax.random.fold_in(root, zlib.crc32(name.encode()))
    return key if index is None else jax.random.fold_in(key, index)


def normal(root, name: str, shape, std: float, index=None):
    """float32 ``N(0, std**2)`` draw of ``shape`` for ``(name, index)``."""
    return jax.random.normal(leaf_key(root, name, index), shape,
                             jnp.float32) * std


def stacked_normal(root, name: str, n: int, shape, std: float):
    """``n`` slices of :func:`normal` stacked on a leading axis, slice ``i``
    equal to ``normal(root, name, shape, std, index=i)``."""
    return jax.vmap(lambda i: normal(root, name, shape, std, index=i))(
        jnp.arange(n))
