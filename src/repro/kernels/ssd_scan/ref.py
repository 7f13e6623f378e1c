"""Pure-jnp oracle for the Mamba2 SSD chunked scan.

Inputs (pre-chunked): x (b,nc,Q,H,P), dt (b,nc,Q,H), B,C (b,nc,Q,N),
la = dt * A (log-decay per step) (b,nc,Q,H), D (H,).
Returns y (b, nc*Q, H, P) and final state (b, H, N, P) — the contract of
models/mamba2.ssd_chunked.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan_ref(x, dt, B, C, la, D):
    b, nc, Q, H, P = x.shape
    N = B.shape[-1]
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def scan_fn(h, inp):
        la_c, x_c, b_c, c_c, dt_c = inp
        lcum = jnp.cumsum(la_c, axis=1)
        seg = lcum[:, :, None, :] - lcum[:, None, :, :]
        # masked before the exponential, as in models/mamba2.ssd_chunked:
        # an overflowed exp(seg) above the diagonal makes the VJP NaN
        L = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bin,bjn->bij", c_c, b_c)
        w = cb[..., None] * L
        xdt = x_c.astype(jnp.float32) * dt_c[..., None]
        y = jnp.einsum("bijh,bjhp->bihp", w, xdt)
        y = y + jnp.einsum("bin,bhnp->bihp", c_c, h) * jnp.exp(lcum)[..., None]
        decay_to_end = jnp.exp(lcum[:, -1:, :] - lcum)
        s_c = jnp.einsum("bjn,bjhp->bhnp", b_c, xdt * decay_to_end[..., None])
        h_new = h * jnp.exp(lcum[:, -1, :])[..., None, None] + s_c
        return h_new, y

    h0 = jnp.zeros((b, H, N, P), jnp.float32)
    xs = (jnp.moveaxis(la, 1, 0), jnp.moveaxis(x, 1, 0),
          jnp.moveaxis(B.astype(jnp.float32), 1, 0),
          jnp.moveaxis(C.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt, 1, 0))
    h_last, ys = jax.lax.scan(scan_fn, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype).reshape(b, nc * Q, H, P)
    y = y + (D[:, None] * x.astype(jnp.float32).reshape(b, nc * Q, H, P)
             ).astype(x.dtype)
    return y, h_last
