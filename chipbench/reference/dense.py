"""Plain reference of a dense decoder (Llama-style, as SmolLM publishes it).

Pre-norm blocks: RMSNorm, grouped-query causal attention with rotary
positions (rotate-half form, base ``rope_theta``), residual; RMSNorm, SwiGLU
MLP, residual. A final RMSNorm and a head tied to the token embedding. The
loss is the mean next-token cross-entropy over every position.

``init_params`` is the benchmark's weight generator: one call from a key
gives every leaf, laid out as the program under test stores its parameters
(layers stacked on a leading axis, attention weights split per head, the
embedding padded to a multiple of 128 rows). Layout is the only thing taken
from the program; nothing here imports it.

Departures from the published model: none in the mathematics. The padded
embedding rows never enter the loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import normal, padded_vocab, rmsnorm, xent


def dims(conf: dict) -> dict:
    """The sizes this reference reads, from the published config's keys."""
    return {"d_model": conf["hidden_size"], "n_layers": conf["num_hidden_layers"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "d_ff": conf["intermediate_size"], "vocab_size": conf["vocab_size"],
            "rope_theta": conf["rope_theta"], "norm_eps": conf["rms_norm_eps"],
            "init_std": conf["initializer_range"]}


def init_params(key, m: dict, dtype):
    D, L = m["d_model"], m["n_layers"]
    H, KV, F = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    hd = D // H
    std = m["init_std"]
    k_emb, k_layers = jax.random.split(key)
    ks = jax.random.split(k_layers, 7)
    return {
        "embed": normal(k_emb, (padded_vocab(m["vocab_size"]), D), std, dtype),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "attn": {"q": normal(ks[0], (L, D, H, hd), std, dtype),
                     "k": normal(ks[1], (L, D, KV, hd), std, dtype),
                     "v": normal(ks[2], (L, D, KV, hd), std, dtype),
                     "o": normal(ks[3], (L, H, hd, D), std, dtype)},
            "ln2": jnp.ones((L, D), jnp.float32),
            "mlp": {"gate": normal(ks[4], (L, D, F), std, dtype),
                    "up": normal(ks[5], (L, D, F), std, dtype),
                    "down": normal(ks[6], (L, F, D), std, dtype)},
        },
        "final_norm": jnp.ones((D,), jnp.float32),
    }


def _rope(x, theta):
    """x: (B, S, heads, hd). Rotate-half rotary embedding."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(p, x, m):
    B, S, _ = x.shape
    H, KV = m["n_heads"], m["n_kv_heads"]
    hd = p["q"].shape[-1]
    q = _rope(jnp.einsum("bsd,dhk->bshk", x, p["q"]), m["rope_theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", x, p["k"]), m["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"])
    # query head h reads key/value head h // (H // KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqt,bthk->bqhk", w, v)
    return jnp.einsum("bshk,hkd->bsd", o, p["o"])


def logits(params, m: dict, tokens, dtype=jnp.float32):
    """Float32 logits over the real vocabulary; activations and matrices in ``dtype``, norm
    statistics and the softmax over the vocabulary in float32."""
    eps, V = m["norm_eps"], m["vocab_size"]
    emb = params["embed"][:V].astype(dtype)
    h = emb[tokens]

    @jax.checkpoint
    def block(h, lp):
        lp = jax.tree.map(lambda a: a.astype(dtype) if a.ndim > 1 else a, lp)
        h = h + _attention(lp["attn"], rmsnorm(h, lp["ln1"], eps), m)
        x = rmsnorm(h, lp["ln2"], eps)
        g = jnp.einsum("bsd,df->bsf", x, lp["mlp"]["gate"])
        u = jnp.einsum("bsd,df->bsf", x, lp["mlp"]["up"])
        h = h + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, lp["mlp"]["down"])
        return h, None

    h, _ = jax.lax.scan(block, h, params["layers"])
    h = rmsnorm(h, params["final_norm"], eps)
    return jnp.einsum("bsd,vd->bsv", h, emb).astype(jnp.float32)


def loss(params, m: dict, tokens, targets, dtype=jnp.float32):
    return xent(logits(params, m, tokens, dtype), targets)
