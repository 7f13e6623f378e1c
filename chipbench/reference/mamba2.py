"""Plain reference of the published Mamba-2 language model (Dao & Gu,
"Transformers are SSMs", arXiv 2405.21060; ``state-spaces/mamba``'s
``Mamba2`` block).

The block is ``ssm.py``'s, with the published gated norm: RMSNormGated
with ``norm_before_gate=False`` and one group over all ``d_inner``
channels, that is RMSNorm(y * silu(z)) * w, where ``ssm.py`` keeps the
departure RMSNorm(y) * silu(z) it notes. The weights (``init_params``,
the published initialisation), the sizes read from a configuration
(``dims``), the causal convolution and the quadratic form of the SSD are
``ssm.py``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import rmsnorm, xent
from .ssm import KEEP_F32, _causal_conv, dims, init_params, ssd  # noqa: F401


def logits(params, m: dict, tokens, dtype=jnp.float32):
    """Float32 logits over the real vocabulary; activations and matrices in
    ``dtype``; norm statistics, dt, the decay and the vocabulary softmax in
    float32."""
    eps, V, N = m["norm_eps"], m["vocab_size"], m["d_state"]
    emb = params["embed"][:V].astype(dtype)
    h = emb[tokens]

    @jax.checkpoint
    def block(h, lp):
        lp = {k: a if k in KEEP_F32 else a.astype(dtype)
              for k, a in lp.items()}
        Bsz, S, _ = h.shape
        Din = lp["out_proj"].shape[0]
        H = lp["A_log"].shape[0]
        u = rmsnorm(h, lp["ln"], eps)
        z = jnp.einsum("bsd,de->bse", u, lp["in_z"])
        x = _causal_conv(jnp.einsum("bsd,de->bse", u, lp["in_x"]),
                         lp["conv_x"], lp["conv_x_b"])
        bc = _causal_conv(jnp.einsum("bsd,de->bse", u, lp["in_bc"]),
                          lp["conv_bc"], lp["conv_bc_b"])
        dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", u, lp["in_dt"])
                             .astype(jnp.float32) + lp["dt_bias"])
        x = x.reshape(Bsz, S, H, Din // H)
        y = ssd(x, dt, -jnp.exp(lp["A_log"]), bc[..., :N], bc[..., N:],
                m["ref_head_group"])
        y = y + (lp["D"][:, None] * x.astype(jnp.float32)).astype(dtype)
        y = rmsnorm(y.reshape(Bsz, S, Din) * jax.nn.silu(z), lp["norm_w"])
        return h + jnp.einsum("bse,ed->bsd", y, lp["out_proj"]), None

    h, _ = jax.lax.scan(block, h, params["layers"])
    h = rmsnorm(h, params["final_norm"], eps)
    return jnp.einsum("bsd,vd->bsv", h, emb).astype(jnp.float32)


def loss(params, m: dict, tokens, targets, dtype=jnp.float32):
    return xent(logits(params, m, tokens, dtype), targets)
