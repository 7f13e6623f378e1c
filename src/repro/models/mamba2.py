"""Mamba2 / SSD (state-space duality, arXiv:2405.21060) block in pure JAX.

Chunked SSD forward (train & prefill): intra-chunk quadratic term + inter-
chunk first-order recurrence over chunk states (lax.scan over chunks).
Single-token recurrent decode against a (conv window, SSM state) cache.

Projection layout (perf iteration 1, EXPERIMENTS.md §Perf): x/z/B/C/dt are
SEPARATE projections rather than one fused in_proj. A fused projection puts
head-shardable channels (x, z) and head-SHARED channels (B, C) in one
tensor-parallel-sharded output, forcing an activation reshard every layer;
split projections keep the SSD entirely head-local under TP (B/C replicate,
x/z shard on the head axis).

The chunk-local compute is mirrored by the Pallas kernel in
kernels/ssd_scan (cfg.use_ssd_kernel routes through it).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .layers import _init, rmsnorm


def mamba2_init(rng, d_model, *, expand=2, headdim=64, ssm_state=128,
                conv_dim=4, dtype=jnp.bfloat16):
    d_inner = expand * d_model
    H = d_inner // headdim
    N = ssm_state
    ks = jax.random.split(rng, 7)
    s = 1.0 / math.sqrt(d_model)
    p = {
        "in_x": _init(ks[0], (d_model, d_inner), s, dtype),
        "in_z": _init(ks[1], (d_model, d_inner), s, dtype),
        "in_bc": _init(ks[2], (d_model, 2 * N), s, dtype),
        "in_dt": _init(ks[3], (d_model, H), s, dtype),
        "conv_x": _init(ks[4], (conv_dim, d_inner), 0.5, dtype),
        "conv_x_b": jnp.zeros((d_inner,), dtype),
        "conv_bc": _init(ks[5], (conv_dim, 2 * N), 0.5, dtype),
        "conv_bc_b": jnp.zeros((2 * N,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),          # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),   # softplus(-2) ~ 0.13
        "norm_w": jnp.ones((d_inner,), jnp.float32),
        "out_proj": _init(ks[6], (d_inner, d_model), 1.0 / math.sqrt(d_inner), dtype),
    }
    ax = {
        "in_x": ("embed", "mlp"),
        "in_z": ("embed", "mlp"),
        "in_bc": ("embed", None),      # B/C are shared across heads: replicate
        "in_dt": ("embed", "heads"),
        "conv_x": ("conv", "mlp"),
        "conv_x_b": ("mlp",),
        "conv_bc": ("conv", None),
        "conv_bc_b": (None,),
        "A_log": ("heads",),
        "D": ("heads",),
        "dt_bias": ("heads",),
        "norm_w": ("mlp",),
        "out_proj": ("mlp", "embed"),
    }
    return p, ax


def _causal_conv(x, w, b):
    """Depthwise causal conv, window K. x: (B, S, C); w: (K, C)."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def ssd_chunked(x, dt, B, C, A_log, D, chunk: int, use_kernel: bool = False):
    """SSD scan. x: (b, S, H, P); dt: (b, S, H); B, C: (b, S, N).
    Returns y: (b, S, H, P) and final state (b, H, N, P)."""
    b, S, H, Pd = x.shape
    N = B.shape[-1]
    nc = S // chunk
    A = -jnp.exp(A_log)                                     # (H,)
    dt32 = dt.astype(jnp.float32)
    la = (dt32 * A).reshape(b, nc, chunk, H)                # log decay / step
    xr = x.reshape(b, nc, chunk, H, Pd)
    Br = B.reshape(b, nc, chunk, N).astype(jnp.float32)
    Cr = C.reshape(b, nc, chunk, N).astype(jnp.float32)
    dtr = dt32.reshape(b, nc, chunk, H)

    if use_kernel:
        from ..kernels.ssd_scan import ops as ssd_ops
        return ssd_ops.ssd_scan(xr, dtr, Br, Cr, la, D)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def scan_fn(h, inp):
        # one chunk at a time: peak temp is (b,Q,Q,H) not (b,nc,Q,Q,H)
        la_c, x_c, b_c, c_c, dt_c = inp            # (b,Q,H) (b,Q,H,P) (b,Q,N)...
        lcum = jnp.cumsum(la_c, axis=1)            # (b,Q,H)
        seg = lcum[:, :, None, :] - lcum[:, None, :, :]      # (b,Q,Q,H)
        # masked before the exponential: above the diagonal seg is large
        # and positive, exp(seg) overflows, and the gradient of a where
        # over an inf is NaN
        L = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bin,bjn->bij", c_c, b_c)            # (b,Q,Q)
        w = cb[..., None] * L                                # (b,Q,Q,H)
        xdt = x_c.astype(jnp.float32) * dt_c[..., None]      # (b,Q,H,P)
        y = jnp.einsum("bijh,bjhp->bihp", w, xdt)            # intra-chunk
        y = y + jnp.einsum("bin,bhnp->bihp", c_c, h) * \
            jnp.exp(lcum)[..., None]                         # inter-chunk
        decay_to_end = jnp.exp(lcum[:, -1:, :] - lcum)       # (b,Q,H)
        s_c = jnp.einsum("bjn,bjhp->bhnp", b_c, xdt * decay_to_end[..., None])
        h_new = h * jnp.exp(lcum[:, -1, :])[..., None, None] + s_c
        return h_new, y

    h0 = jnp.zeros((b, H, N, Pd), jnp.float32)
    xs = (jnp.moveaxis(la, 1, 0), jnp.moveaxis(xr, 1, 0),
          jnp.moveaxis(Br, 1, 0), jnp.moveaxis(Cr, 1, 0),
          jnp.moveaxis(dtr, 1, 0))
    with jax.named_scope("ssd_scan"):
        h_last, ys = jax.lax.scan(scan_fn, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype).reshape(b, S, H, Pd)
    y = y + (D[:, None] * x.astype(jnp.float32)).astype(x.dtype)
    return y, h_last


class MambaCache(NamedTuple):
    conv_x: jax.Array   # (B, K-1, d_inner) last inputs to the x conv
    conv_bc: jax.Array  # (B, K-1, 2N)
    h: jax.Array        # (B, H, N, P) SSM state

    @staticmethod
    def init(batch, d_model, *, expand=2, headdim=64, ssm_state=128,
             conv_dim=4, dtype=jnp.bfloat16):
        d_inner = expand * d_model
        H = d_inner // headdim
        return MambaCache(
            conv_x=jnp.zeros((batch, conv_dim - 1, d_inner), dtype),
            conv_bc=jnp.zeros((batch, conv_dim - 1, 2 * ssm_state), dtype),
            h=jnp.zeros((batch, H, ssm_state, headdim), jnp.float32),
        )


def _last_inputs(x, K):
    """The last K-1 positions of x (B, S, C), zero rows before the first."""
    return jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))[:, x.shape[1]:]


def _gated_norm(y, z, w):
    """Mamba-2's RMSNormGated (norm_before_gate=False, one group): the gate
    is applied first and the product normalised."""
    return rmsnorm(y * jax.nn.silu(z), w)


def mamba2_forward(p, u, *, chunk=256, use_kernel=False):
    """u: (B, S, D) -> (B, S, D); returns (out, the cache a decode continues
    from: the last conv inputs and the final SSM state)."""
    Bsz, S, Dm = u.shape
    d_inner = p["out_proj"].shape[0]
    H = p["A_log"].shape[0]
    Pd = d_inner // H
    N = p["in_bc"].shape[1] // 2
    K = p["conv_x"].shape[0]
    z = jnp.einsum("bsd,de->bse", u, p["in_z"])
    x_in = jnp.einsum("bsd,de->bse", u, p["in_x"])
    bc_in = jnp.einsum("bsd,de->bse", u, p["in_bc"])
    dt = jnp.einsum("bsd,dh->bsh", u, p["in_dt"])
    x = _causal_conv(x_in, p["conv_x"], p["conv_x_b"]).reshape(Bsz, S, H, Pd)
    bc = _causal_conv(bc_in, p["conv_bc"], p["conv_bc_b"])
    Bm, Cm = bc[..., :N], bc[..., N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    y, h_last = ssd_chunked(x, dt, Bm, Cm, p["A_log"], p["D"], chunk,
                            use_kernel=use_kernel)
    y = _gated_norm(y.reshape(Bsz, S, d_inner), z, p["norm_w"])
    cache = MambaCache(_last_inputs(x_in, K), _last_inputs(bc_in, K), h_last)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), cache


def mamba2_decode(p, u, cache: MambaCache):
    """u: (B, D) single token. Returns (out (B, D), new cache)."""
    Bsz, Dm = u.shape
    d_inner = p["out_proj"].shape[0]
    H = p["A_log"].shape[0]
    Pd = d_inner // H
    N = p["in_bc"].shape[1] // 2
    z = jnp.einsum("bd,de->be", u, p["in_z"])
    x = jnp.einsum("bd,de->be", u, p["in_x"])
    bc = jnp.einsum("bd,de->be", u, p["in_bc"])
    dt = jnp.einsum("bd,dh->bh", u, p["in_dt"])
    # causal conv over (cached K-1 inputs, current token)
    wx = jnp.concatenate([cache.conv_x, x[:, None, :]], axis=1)   # (B,K,C)
    x = jax.nn.silu(jnp.einsum("bkc,kc->bc", wx, p["conv_x"]) + p["conv_x_b"])
    wbc = jnp.concatenate([cache.conv_bc, bc[:, None, :]], axis=1)
    bc = jax.nn.silu(jnp.einsum("bkc,kc->bc", wbc, p["conv_bc"]) + p["conv_bc_b"])
    x = x.reshape(Bsz, H, Pd).astype(jnp.float32)
    Bm = bc[..., :N].astype(jnp.float32)
    Cm = bc[..., N:].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])      # (B,H)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * A)                                          # (B,H)
    xdt = x * dt[..., None]                                          # (B,H,P)
    h_new = cache.h * decay[..., None, None] + \
        jnp.einsum("bn,bhp->bhnp", Bm, xdt)
    y = jnp.einsum("bn,bhnp->bhp", Cm, h_new) + p["D"][:, None] * x
    y = _gated_norm(y.reshape(Bsz, d_inner).astype(u.dtype), z, p["norm_w"])
    return jnp.einsum("be,ed->bd", y, p["out_proj"]), \
        MambaCache(wx[:, 1:, :], wbc[:, 1:, :], h_new)
