"""Plain AdamW with global-norm clipping and a warmup-cosine schedule.

Loshchilov & Hutter, "Decoupled Weight Decay Regularization" (arXiv
1711.05101): the decay is applied to the parameter, scaled by the learning
rate, outside the adaptive step. Gradients are clipped by their global norm
before the moments see them. Written from the paper, independent of the
program's optimizer; every quantity is float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def learning_rate(opt: dict, count: int) -> float:
    """Linear warmup to ``lr`` over ``warmup_steps``, then a cosine from
    ``lr`` down to ``lr * min_lr_frac`` at ``total_steps``."""
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    prog = min(max((count - opt["warmup_steps"]) / span, 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * cos)


def init_moments(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return jax.tree.map(zeros, params), jax.tree.map(zeros, params)


def clip_scale(grads, clip: float):
    """The factor the clipped gradient is scaled by, and the global norm."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    return jnp.minimum(1.0, clip / (norm + 1e-9)), norm


def update(params, grads, m, v, lr, count, scale, opt: dict):
    """One AdamW step; ``count`` is the 1-based step number and ``lr`` its
    learning rate, both passed as arrays so one compiled program serves every
    step. Parameters keep their own dtype, moments stay float32."""
    b1, b2 = opt["b1"], opt["b2"]
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count

    def one(p, g, mi, vi):
        g = g.astype(jnp.float32) * scale
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        p32 = p.astype(jnp.float32)
        step = (mi / c1) / (jnp.sqrt(vi / c2) + opt["eps"]) \
            + opt["weight_decay"] * p32
        return (p32 - lr * step).astype(p.dtype), mi, vi

    out = jax.tree.map(one, params, grads, m, v)
    is3 = lambda x: isinstance(x, tuple) and len(x) == 3
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=is3)
    return pick(0), pick(1), pick(2)
