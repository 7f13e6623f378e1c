"""Mixture-of-Experts FFN (mixtral-style top-k routed + qwen-style shared
experts) with sort-based token dispatch and capacity dropping.

Dispatch runs *locally per data shard* under shard_map so the token sort
never becomes a global collective; the only cross-device communication is
the tensor-parallel psum of the down-projection (contracting dim sharded
over "model"). When no mesh context is active (CPU smoke tests) the same
function runs unpartitioned.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed import current_mesh, batch_axes
from ..distributed.sharding import current_rules
from .layers import _init, mlp_init, mlp_apply


def moe_init(rng, d_model, moe_d_ff, n_experts, dtype, shared_d_ff=0):
    ks = jax.random.split(rng, 5)
    s = 1.0 / math.sqrt(d_model)
    p = {
        "router": _init(ks[0], (d_model, n_experts), s, jnp.float32),
        "gate": _init(ks[1], (n_experts, d_model, moe_d_ff), s, dtype),
        "up": _init(ks[2], (n_experts, d_model, moe_d_ff), s, dtype),
        "down": _init(ks[3], (n_experts, moe_d_ff, d_model),
                      1.0 / math.sqrt(moe_d_ff), dtype),
    }
    ax = {
        "router": ("embed", "experts"),
        "gate": ("experts", "embed", "mlp"),
        "up": ("experts", "embed", "mlp"),
        "down": ("experts", "mlp", "embed"),
    }
    if shared_d_ff:
        p["shared"], ax["shared"] = mlp_init(ks[4], d_model, shared_d_ff, dtype)
    return p, ax


def _dispatch_ffn(p, xt, n_top: int, capacity_factor: float, tp_axis):
    """xt: (T, D) local tokens. Returns (T, D). Runs inside shard_map (or
    unpartitioned when tp_axis is None)."""
    T, D = xt.shape
    E = p["router"].shape[1]
    logits = (xt.astype(jnp.float32) @ p["router"])            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, n_top)                    # (T, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    flat_e = topi.reshape(-1)                                   # (T*k,)
    flat_w = topv.reshape(-1)
    flat_t = jnp.arange(T * n_top, dtype=jnp.int32) // n_top
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=E)                     # (E,)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * n_top, dtype=jnp.int32) - starts[se]
    C = max(1, int(math.ceil(capacity_factor * T * n_top / E)))
    keep = rank < C
    dst = jnp.where(keep, se * C + rank, E * C)                 # drop row E*C
    buf = jnp.zeros((E * C + 1, D), xt.dtype).at[dst].set(xt[st])
    xe = buf[: E * C].reshape(E, C, D)
    g = jnp.einsum("ecd,edf->ecf", xe, p["gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["up"])
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["down"])
    contrib = jnp.concatenate(
        [ye.reshape(E * C, D), jnp.zeros((1, D), ye.dtype)])[dst]
    contrib = contrib * (sw * keep)[:, None].astype(ye.dtype)
    y = jnp.zeros((T, D), ye.dtype).at[st].add(contrib)
    if tp_axis is not None:
        # TP reduction AFTER the scatter-back: psum the (T, D) output, not
        # the (E, C, D) dispatch buffer — k*capacity_factor*x less traffic
        # (everything between the partial down-proj and here is linear, so
        # the reordering is exact). Perf iteration 6, EXPERIMENTS.md §Perf.
        y = jax.lax.psum(y, tp_axis)
    # load-balance auxiliary loss (Switch-style), returned for logging
    frac_tokens = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32),
                           axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y, aux


def moe_apply(p, x, *, n_top: int, capacity_factor: float = 1.25,
              batch_replicated: bool = False):
    """x: (B, S, D) -> (B, S, D). Shared experts (if present) are added."""
    B, S, D = x.shape
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.shape:
        y, aux = _dispatch_ffn(p, x.reshape(B * S, D), n_top,
                               capacity_factor, None)
        y = y.reshape(B, S, D)
    else:
        rules = current_rules()
        bax = batch_axes(mesh, B)  # () when B doesn't divide -> replicate
        bax = bax if bax else None
        E, Dm, F = p["gate"].shape
        mlp_ax = rules.get("mlp")
        tp = mlp_ax if isinstance(mlp_ax, str) else None
        if not (tp and tp in mesh.shape and F % mesh.shape[tp] == 0):
            tp = None
        wspec = {
            "router": P(None, None),
            "gate": P(None, None, tp),
            "up": P(None, None, tp),
            "down": P(None, tp, None),
        }
        xspec = P(bax, None, None)

        def body(pw, xl):
            Bl, Sl, Dl = xl.shape
            yl, aux = _dispatch_ffn(pw, xl.reshape(Bl * Sl, Dl), n_top,
                                    capacity_factor, tp)
            if tp is None:
                # weights replicated over model: outputs identical; no psum
                pass
            return yl.reshape(Bl, Sl, Dl), aux

        pw = {k: p[k] for k in ("router", "gate", "up", "down")}
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(wspec, xspec),
            out_specs=(xspec, P()), check_vma=False,
        )(pw, x)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x)
    return y, aux
