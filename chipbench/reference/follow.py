"""The reference follows a training run's first steps.

From the same seed, sizes and batches as the program, it builds its own
weights, takes each step's loss and gradient with a family's plain
reference and applies plain AdamW. It returns what the comparison needs:
each step's loss and global gradient norm before clipping, the norm of each
leaf of the first clipped gradient, and the norm of each leaf's change over
all the steps.

The batch is taken ``row_block`` rows at a time and the gradients summed,
weighted by rows, so that activations fit beside the train state; every row
has the same number of positions, so this is the mean over the batch.

Given several ``devices``, the reference spreads its weights, moments and
gradients over them by a placement of its own, never the program's rules:
each leaf split along its largest dimension that the number of devices
divides, and whole on every device where none does. XLA partitions the
computation to match; the mathematics is the same.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import adamw


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm} for every leaf of ``tree``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32)))) for path, leaf in flat}


def to_floats(norms: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def spread(like, devices):
    """(a sharding per leaf of ``like``, the replicated sharding) over a
    one-axis mesh of ``devices``: each leaf split along its largest
    dimension that ``len(devices)`` divides, replicated where none does."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("chips",))
    n = len(devices)

    def one(leaf):
        dims = [d for d, size in enumerate(leaf.shape) if size % n == 0]
        if not dims:
            return NamedSharding(mesh, P())
        d = max(dims, key=lambda d: leaf.shape[d])
        return NamedSharding(mesh, P(*(None,) * d, "chips"))
    return jax.tree.map(one, like), NamedSharding(mesh, P())


def follow(family, m: dict, opt: dict, key, batches, *, compute_dtype,
           param_dtype, row_block: int, precision: str = "highest",
           devices=None):
    """``batches``: [(tokens, targets)] for steps 1, 2, ...; int32 arrays
    of shape (rows, seq). ``devices``: where the state lives, spread over
    them as the module says; by default JAX's default device holds it."""
    sh = whole = None
    if devices is not None:
        sh, whole = spread(jax.eval_shape(
            lambda: family.init_params(key, m, param_dtype)), devices)
    pin = lambda out: {} if sh is None else {"out_shardings": out}
    init = jax.jit(lambda k: family.init_params(k, m, param_dtype),
                   **pin(sh))
    value_grad = jax.jit(jax.value_and_grad(
        lambda p, t, y: family.loss(p, m, t, y, compute_dtype)),
        **pin((whole, sh)))
    scaled = jax.jit(lambda g, w: jax.tree.map(lambda a: a * w, g),
                     **pin(sh))
    accumulate = jax.jit(lambda g, gb, w: jax.tree.map(
        lambda a, b: a + b * w, g, gb), donate_argnums=(0,), **pin(sh))
    clip = jax.jit(lambda g: adamw.clip_scale(g, opt["grad_clip"]))
    first_grad = jax.jit(lambda g, s: leaf_norms(
        jax.tree.map(lambda a: a * s, g)))
    step = jax.jit(lambda p, g, mi, vi, lr, n, s: adamw.update(
        p, g, mi, vi, lr, n, s, opt), donate_argnums=(0, 1, 2, 3),
        **pin((sh, sh, sh)))
    change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, init(k))))

    losses, gnorms, grad_norms = [], [], None
    with jax.default_matmul_precision(precision):
        params = init(key)
        mom, vel = adamw.init_moments(params) if sh is None else jax.jit(
            adamw.init_moments, out_shardings=(sh, sh))(params)
        for count, (tokens, targets) in enumerate(batches, start=1):
            rows = tokens.shape[0]
            grads, loss = None, 0.0
            for r in range(0, rows, row_block):
                lb, gb = value_grad(params, tokens[r:r + row_block],
                                    targets[r:r + row_block])
                w = jnp.float32(min(row_block, rows - r) / rows)
                grads = scaled(gb, w) if grads is None \
                    else accumulate(grads, gb, w)
                del gb
                loss += float(lb) * float(w)
            losses.append(loss)
            s, gnorm = clip(grads)
            gnorms.append(float(gnorm))
            if count == 1:
                grad_norms = to_floats(first_grad(grads, s))
            params, mom, vel = step(
                params, grads, mom, vel,
                jnp.float32(adamw.learning_rate(opt, count)),
                jnp.float32(count), s)
            del grads
        del mom, vel
        change_norms = to_floats(change(params, key))
    return {"losses": losses, "gnorms": gnorms, "grad_norms": grad_norms,
            "change_norms": change_norms}
