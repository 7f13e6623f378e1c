"""Pieces the plain references share."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def padded_vocab(v: int, multiple: int = 128) -> int:
    """Rows of the stored embedding: the vocabulary rounded up."""
    return -(-v // multiple) * multiple


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def rmsnorm(x, w, eps=1e-5):
    """Root-mean-square norm; the statistic in float32, the result in the
    input's dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, high bits included."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def xent(logits, targets):
    """Mean next-token cross-entropy over every position."""
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
