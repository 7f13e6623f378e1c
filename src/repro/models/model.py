"""Uniform model facade: init / loss / prefill / decode_step for every
assigned architecture family.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..distributed import shard_activation
from .layers import (_init, embed_init, pad_vocab, rmsnorm, rmsnorm_init,
                     softmax_xent)
from .mamba2 import MambaCache, mamba2_decode, mamba2_forward, mamba2_init
from .transformer import (DecodeState, transformer_decode_step,
                          transformer_init, transformer_loss,
                          transformer_prefill)
from .zamba2 import (HybridState, zamba2_decode_step, zamba2_forward,
                     zamba2_init, zamba2_init_state)


# --------------------------------------------------------------------------
# Pure-SSM LM (mamba2-2.7b)
# --------------------------------------------------------------------------
def ssm_init(rng, cfg):
    dtype = cfg.dtype
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    vpad = pad_vocab(cfg.vocab_size)
    params, axes = {}, {}
    params["embed"], axes["embed"] = embed_init(k_emb, vpad, cfg.d_model, dtype)
    lkeys = jax.random.split(k_layers, cfg.n_layers)

    def one(k):
        p, _ = mamba2_init(k, cfg.d_model, expand=cfg.ssm_expand,
                           headdim=cfg.ssm_headdim, ssm_state=cfg.ssm_state,
                           dtype=dtype)
        p["ln"], _ = rmsnorm_init(cfg.d_model)
        return p

    _, ax0 = mamba2_init(jax.random.PRNGKey(0), cfg.d_model,
                         expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
                         ssm_state=cfg.ssm_state, dtype=dtype)
    ax0["ln"] = ("norm",)
    params["layers"] = jax.vmap(one)(lkeys)
    axes["layers"] = jax.tree.map(lambda t: ("layers",) + t, ax0,
                                  is_leaf=lambda x: isinstance(x, tuple))
    params["final_norm"], axes["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = _init(k_head, (cfg.d_model, vpad),
                               1.0 / math.sqrt(cfg.d_model), dtype)
        axes["head"] = ("embed", "vocab")
    return params, axes


def _ssm_backbone(params, cfg, h):
    def body(hh, lp):
        hh = shard_activation(hh)
        out, _ = mamba2_forward(lp, rmsnorm(hh, lp["ln"], cfg.norm_eps),
                                chunk=cfg.ssm_chunk,
                                use_kernel=cfg.use_ssd_kernel)
        return hh + out, None
    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["layers"])
    return h


def _lm_logits(params, cfg, h):
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", h, params["embed"])
    return jnp.einsum("...d,dv->...v", h, params["head"])


def ssm_loss(params, cfg, batch):
    h = shard_activation(jnp.take(params["embed"], batch["tokens"], axis=0))
    h = _ssm_backbone(params, cfg, h)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return softmax_xent(_lm_logits(params, cfg, h), batch["targets"],
                        cfg.vocab_size)


class SSMState(NamedTuple):
    caches: MambaCache  # stacked (L, ...)
    pos: jax.Array


def ssm_prefill(params, cfg, batch, cache_len):
    h = shard_activation(jnp.take(params["embed"], batch["tokens"], axis=0))

    def body(hh, lp):
        hh = shard_activation(hh)
        out, cache = mamba2_forward(lp, rmsnorm(hh, lp["ln"], cfg.norm_eps),
                                    chunk=cfg.ssm_chunk,
                                    use_kernel=cfg.use_ssd_kernel)
        return hh + out, cache
    if cfg.remat:
        body = jax.checkpoint(body)
    h, caches = jax.lax.scan(body, h, params["layers"])
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, h[:, -1])
    S = batch["tokens"].shape[1]
    return logits, SSMState(caches, jnp.asarray(S, jnp.int32))


def ssm_decode_step(params, cfg, state: SSMState, tokens):
    h = shard_activation(jnp.take(params["embed"], tokens, axis=0))

    def body(hh, xs):
        lp, cache = xs
        out, nc = mamba2_decode(lp, rmsnorm(hh, lp["ln"], cfg.norm_eps),
                                MambaCache(*cache))
        return hh + out, tuple(nc)

    h, new = jax.lax.scan(body, h, (params["layers"], tuple(state.caches)))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return _lm_logits(params, cfg, h), SSMState(MambaCache(*new), state.pos + 1)


# --------------------------------------------------------------------------
# Hybrid (zamba2)
# --------------------------------------------------------------------------
def hybrid_loss(params, cfg, batch):
    h = shard_activation(jnp.take(params["embed"], batch["tokens"], axis=0))
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = zamba2_forward(params, cfg, h, positions)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return softmax_xent(_lm_logits(params, cfg, h), batch["targets"],
                        cfg.vocab_size)


def hybrid_prefill(params, cfg, batch, cache_len):
    # prefill = forward + decode-state seeding; for the dry-run we seed the
    # state by running the last token through a decode step after forward.
    h = shard_activation(jnp.take(params["embed"], batch["tokens"], axis=0))
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    hf = zamba2_forward(params, cfg, h, positions)
    hf = rmsnorm(hf, params["final_norm"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, hf[:, -1])
    state = zamba2_init_state(cfg, B, cache_len, cfg.dtype)
    return logits, state


def hybrid_decode_step(params, cfg, state, tokens):
    h = shard_activation(jnp.take(params["embed"], tokens, axis=0))
    h, new_state = zamba2_decode_step(params, cfg, state, h)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return _lm_logits(params, cfg, h), new_state


# --------------------------------------------------------------------------
# Facade
# --------------------------------------------------------------------------
class Model:
    def __init__(self, cfg):
        self.cfg = cfg

    def init(self, rng):
        f = self.cfg.family
        if f == "ssm":
            return ssm_init(rng, self.cfg)
        if f == "hybrid":
            return zamba2_init(rng, self.cfg)
        return transformer_init(rng, self.cfg)

    def loss(self, params, batch):
        f = self.cfg.family
        if f == "ssm":
            return ssm_loss(params, self.cfg, batch)
        if f == "hybrid":
            return hybrid_loss(params, self.cfg, batch)
        return transformer_loss(params, self.cfg, batch)

    def prefill(self, params, batch, cache_len):
        f = self.cfg.family
        if f == "ssm":
            return ssm_prefill(params, self.cfg, batch, cache_len)
        if f == "hybrid":
            return hybrid_prefill(params, self.cfg, batch, cache_len)
        return transformer_prefill(params, self.cfg, batch, cache_len)

    def decode_step(self, params, state, tokens):
        f = self.cfg.family
        if f == "ssm":
            return ssm_decode_step(params, self.cfg, state, tokens)
        if f == "hybrid":
            return hybrid_decode_step(params, self.cfg, state, tokens)
        return transformer_decode_step(params, self.cfg, state, tokens)

    def encode(self, params, batch):
        """Encoder-only forward: logits over the whole sequence (hubert)."""
        from .transformer import _embed_inputs, _logits, _scan_layers
        cfg = self.cfg
        h = _embed_inputs(params, cfg, batch)
        B, S, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, _ = _scan_layers(params, cfg, h, positions)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params, cfg, h)

    def decode_state_axes(self):
        """Logical-axes tree matching init_decode_state's structure."""
        cfg = self.cfg
        kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        sp = ("layers", "kv_seq")
        mamba = MambaCache(conv_x=("layers", "batch", "conv", "mlp"),
                           conv_bc=("layers", "batch", "conv", None),
                           h=("layers", "batch", "heads", "state", "head_dim"))
        if cfg.family == "ssm":
            return SSMState(caches=mamba, pos=())
        if cfg.family == "hybrid":
            from .zamba2 import HybridState as HS
            from .attention import KVCache as KC
            return HS(mamba=mamba, attn=KC(k=kv, v=kv, slot_pos=sp), pos=())
        from .attention import KVCache as KC
        return DecodeState(caches=KC(k=kv, v=kv, slot_pos=sp), pos=())

    def init_decode_state(self, batch, cache_len):
        """Decode-state pytree (for dry-run ShapeDtypeStructs)."""
        cfg = self.cfg
        if cfg.family == "ssm":
            base = MambaCache.init(batch, cfg.d_model, expand=cfg.ssm_expand,
                                   headdim=cfg.ssm_headdim,
                                   ssm_state=cfg.ssm_state, dtype=cfg.dtype)
            caches = MambaCache(*jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape),
                tuple(base)))
            return SSMState(caches, jnp.asarray(cache_len, jnp.int32))
        if cfg.family == "hybrid":
            st = zamba2_init_state(cfg, batch, cache_len, cfg.dtype)
            return HybridState(st.mamba, st.attn,
                               jnp.asarray(cache_len, jnp.int32))
        from .transformer import init_cache
        caches = init_cache(cfg, batch, cache_len, cfg.dtype)
        return DecodeState(caches, jnp.asarray(cache_len, jnp.int32))
