"""Checksums of a train state, the same on the device and on the host.

Per leaf, two sums of the leaf's bit patterns as unsigned 32-bit words,
wrapping: the plain sum, and the sum weighted by a multiplicative hash of
each element's position, which also catches elements that moved. Equal
checksums of what was saved and what reads back stand for an exact round
trip without a second copy of the state on the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KNUTH = 2654435761


def _device_words(x):
    x = x.reshape(-1)
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype.itemsize == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    raise TypeError(f"no checksum for {x.dtype}")


def device_checksums(tree):
    """[uint32 (2,) per leaf], in tree order. Jit it."""
    out = []
    for leaf in jax.tree.leaves(tree):
        w = _device_words(leaf)
        pos = jnp.arange(w.size, dtype=jnp.uint32) * jnp.uint32(KNUTH) \
            + jnp.uint32(1)
        out.append(jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                              jnp.sum(w * pos, dtype=jnp.uint32)]))
    return out


def host_checksums(tree) -> list:
    out = []
    for leaf in jax.tree.leaves(tree):
        a = np.ascontiguousarray(np.asarray(leaf)).reshape(-1)
        if a.dtype.itemsize == 4:
            w = a.view(np.uint32)
        elif a.dtype.itemsize == 2:
            w = a.view(np.uint16).astype(np.uint32)
        else:
            raise TypeError(f"no checksum for {a.dtype}")
        pos = np.arange(w.size, dtype=np.uint32) * np.uint32(KNUTH) \
            + np.uint32(1)
        out.append(np.array([np.sum(w, dtype=np.uint32),
                             np.sum(w * pos, dtype=np.uint32)], np.uint32))
    return out
