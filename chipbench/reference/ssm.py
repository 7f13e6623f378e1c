"""Plain reference of a pure Mamba-2 language model (Dao & Gu, "Transformers
are SSMs", arXiv 2405.21060).

Each block: RMSNorm; projections to z, x, B, C and dt; a depthwise causal
convolution of width 4 with SiLU over x and over (B, C); dt = softplus(dt +
dt_bias); the selective state space y = SSD(x, dt, A, B, C) + D x with
A = -exp(A_log), one group of B and C shared by every head; a gated RMSNorm;
the output projection; residual. A final RMSNorm, a head tied to the token
embedding, mean next-token cross-entropy.

The SSD is evaluated in its quadratic ("attention") form from the paper's
section 3: y_i = sum_{j<=i} (C_i . B_j) exp(sum_{k=j+1..i} dt_k A) dt_j x_j,
which is the sequential recurrence h_i = exp(dt_i A) h_{i-1} + dt_i B_i x_i^T,
y_i = C_i . h_i written out, with no chunking.

Departure from the published block, taken from the program under test and
noted: the gated norm is RMSNorm(y) * silu(z), where the published
RMSNormGated computes RMSNorm(y * silu(z)).

``init_params`` is the benchmark's weight generator, laid out as the program
stores its parameters (layers stacked, x/z/BC/dt projections separate, the
convolution split into its x and (B, C) channels, the embedding padded to a
multiple of 128 rows). Initial values follow the published initialisation:
A_log = log U(1, 16), dt_bias = softplus^-1 of dt ~ logU(1e-3, 1e-1), D = 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import normal, padded_vocab, rmsnorm, xent

CONV = 4
# per-head scalars and norm weights, which the program keeps in float32
KEEP_F32 = ("A_log", "D", "dt_bias", "norm_w", "ln")


def dims(conf: dict) -> dict:
    """The sizes this reference reads, from the published config's keys."""
    ssm = conf["ssm_cfg"]
    return {"d_model": conf["d_model"], "n_layers": conf["n_layer"],
            "d_state": ssm["d_state"], "expand": ssm["expand"],
            "headdim": ssm["headdim"], "chunk": ssm["chunk_size"],
            "vocab_size": conf["vocab_size"],
            "norm_eps": conf["norm_epsilon"],
            "init_std": conf["initializer_range"],
            "ref_head_group": conf.get("reference", {}).get("head_group", 1)}


def init_params(key, m: dict, dtype):
    D, L, N = m["d_model"], m["n_layers"], m["d_state"]
    Din = m["expand"] * D
    H = Din // m["headdim"]
    ks = jax.random.split(key, 12)
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(Din) / math.sqrt(L)
    dt = jnp.exp(jax.random.uniform(ks[9], (L, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "embed": normal(ks[0], (padded_vocab(m["vocab_size"]), D),
                        m["init_std"], dtype),
        "layers": {
            "in_x": normal(ks[1], (L, D, Din), s_in, dtype),
            "in_z": normal(ks[2], (L, D, Din), s_in, dtype),
            "in_bc": normal(ks[3], (L, D, 2 * N), s_in, dtype),
            "in_dt": normal(ks[4], (L, D, H), s_in, dtype),
            "conv_x": normal(ks[5], (L, CONV, Din), 0.5, dtype),
            "conv_x_b": jnp.zeros((L, Din), dtype),
            "conv_bc": normal(ks[6], (L, CONV, 2 * N), 0.5, dtype),
            "conv_bc_b": jnp.zeros((L, 2 * N), dtype),
            "A_log": jnp.log(jax.random.uniform(ks[7], (L, H), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((L, H), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm_w": jnp.ones((L, Din), jnp.float32),
            "out_proj": normal(ks[8], (L, Din, D), s_out, dtype),
            "ln": jnp.ones((L, D), jnp.float32),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
    }


def _causal_conv(x, w, b):
    """Depthwise causal convolution. x: (B, S, C); w: (K, C); tap K-1 is
    the current position."""
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (CONV - 1, 0), (0, 0)))
    out = sum(xp[:, k:k + S] * w[k] for k in range(CONV))
    return jax.nn.silu(out + b)


def ssd(x, dt, A, B, C, head_group: int):
    """Quadratic form of the SSD. x: (b, S, H, P); dt: (b, S, H) float32;
    A: (H,); B, C: (b, S, N). Heads are taken ``head_group`` at a time, each
    group recomputed in the backward pass, so one (S, S) decay matrix per
    head of the group is alive at once."""
    b, S, H, P = x.shape
    cb = jnp.einsum("bin,bjn->bij", C, B)                        # (b, S, S)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(args):
        xg, dtg, Ag = args                     # (b,S,G,P) (b,S,G) (G,)
        cum = jnp.cumsum(dtg.astype(jnp.float32) * Ag, axis=1)   # (b,S,G)
        seg = cum[:, :, None, :] - cum[:, None, :, :]            # (b,i,j,G)
        # masked before the exponential: above the diagonal seg is large and
        # positive, and exp(seg) = inf would turn the gradient into NaN
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        w = (cb.astype(jnp.float32)[..., None] * decay).astype(x.dtype)
        xdt = (xg.astype(jnp.float32) * dtg[..., None]).astype(x.dtype)
        return jnp.einsum("bijg,bjgp->bigp", w, xdt)

    G = head_group
    split = lambda t, ax: jnp.moveaxis(
        t.reshape(t.shape[:ax] + (H // G, G) + t.shape[ax + 1:]), ax, 0)
    ys = jax.lax.map(group, (split(x, 2), split(dt, 2), split(A, 0)))
    return jnp.moveaxis(ys, 0, 2).reshape(b, S, H, P)


def logits(params, m: dict, tokens, dtype=jnp.float32):
    """Float32 logits over the real vocabulary; activations and matrices in ``dtype``; norm
    statistics, dt, the decay and the vocabulary softmax in float32."""
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
        y = rmsnorm(y.reshape(Bsz, S, Din), lp["norm_w"]) * jax.nn.silu(z)
        return h + jnp.einsum("bse,ed->bsd", y, lp["out_proj"]), None

    h, _ = jax.lax.scan(block, h, params["layers"])
    h = rmsnorm(h, params["final_norm"], eps)
    return jnp.einsum("bsd,vd->bsv", h, emb).astype(jnp.float32)


def loss(params, m: dict, tokens, targets, dtype=jnp.float32):
    return xent(logits(params, m, tokens, dtype), targets)
