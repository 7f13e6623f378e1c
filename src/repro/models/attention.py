"""GQA/MQA attention with RoPE, causal / bidirectional / sliding-window
masks, full-sequence forward (train & prefill) and single-token decode
against a (optionally rolling) KV cache.

``multihead_attn`` takes one of four full-sequence paths, chosen by
``attention_path`` from what the code can observe (backend, mask, shape);
``use_flash`` forces the first:

- ``"flash"``: the Pallas flash-attention kernels
  (``kernels/flash_attention``), forward and backward, which keep the
  scores in VMEM and skip the blocks the mask hides. Taken on the TPU for
  causal masks (with or without a window) where ``S`` is a multiple of the
  kernels' block and at least two of them, ``hd`` is 64 or 128, and no
  multi-device mesh is current (XLA does not partition a Pallas call). On
  the CPU it runs only when forced, in interpret mode.
- ``"chunked"``: ``S >= chunk_q_threshold`` (a multiple of ``chunk_q``):
  ``_chunked_attn``, a scan over query chunks that bounds the score
  temporary at long sequences;
- ``"causal_chunks"``: causal with ``S`` a multiple of ``CAUSAL_CHUNK``
  and at least two chunks: ``_causal_chunked_attn``, where each query chunk
  attends only to the keys it can see, so the masked upper triangle is not
  computed;
- ``"dense"``: everything else (bidirectional, short sequences):
  ``_dense_attn``, the lowering used by the dry-run/roofline.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..distributed import current_mesh
from ..kernels.flash_attention import ops as flash_ops
from .layers import _init, apply_rope

# Query chunk of the causal key-truncated path: chunk i attends to keys
# [lo_i, (i+1) * CAUSAL_CHUNK), so about (n+1)/(2n) of the S x S scores
# are computed for n chunks. On a TPU v5e, training SmolLM-360M at
# 4 x 2048, 256 ran about 20% faster than 512.
CAUSAL_CHUNK = 256


def attn_init(rng, d_model, n_heads, n_kv, head_dim, dtype):
    kq, kk, kv, ko = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d_model)
    p = {
        "q": _init(kq, (d_model, n_heads, head_dim), s, dtype),
        "k": _init(kk, (d_model, n_kv, head_dim), s, dtype),
        "v": _init(kv, (d_model, n_kv, head_dim), s, dtype),
        "o": _init(ko, (n_heads, head_dim, d_model), 1.0 / math.sqrt(n_heads * head_dim), dtype),
    }
    ax = {
        "q": ("embed", "heads", "head_dim"),
        "k": ("embed", "kv_heads", "head_dim"),
        "v": ("embed", "kv_heads", "head_dim"),
        "o": ("heads", "head_dim", "embed"),
    }
    return p, ax


def _mask(q_pos, k_pos, causal: bool, window: int):
    """(..., Sq, Sk) boolean mask. window=0 -> unbounded."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool) \
        if not causal else (k_pos[..., None, :] <= q_pos[..., :, None])
    if window:
        m = m & (k_pos[..., None, :] > q_pos[..., :, None] - window)
    return m


def _dense_attn(q, k, v, q_pos, k_pos, causal, window):
    """Materialises the full (Sq, Sk) score matrix, masked entries included.
    Taken for bidirectional attention and for causal sequences shorter than
    two ``CAUSAL_CHUNK``s or not a multiple of it; ``_causal_chunked_attn``
    runs it per query chunk over the keys that chunk can see."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k) / math.sqrt(hd)
    mask = _mask(q_pos, k_pos, causal, window)              # (B, Sq, Sk)
    scores = jnp.where(mask[:, None, None], scores.astype(jnp.float32), -1e9)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkh->bskgh", w, v).reshape(B, Sq, H, hd)


def causal_key_range(i, chunk, window):
    """Keys [lo, hi) that query chunk ``i`` can see under a causal mask
    (and a sliding window of ``window`` positions, 0 for none), with ``lo``
    rounded down to a multiple of ``chunk``."""
    lo = max(0, i * chunk - window + 1) if window else 0
    return lo - lo % chunk, (i + 1) * chunk


def _causal_chunked_attn(q, k, v, positions, window, chunk):
    """Causal attention without the masked upper triangle: query chunk i
    is ``_dense_attn`` over only the keys ``causal_key_range`` gives it.
    The chunks differ in key length, so they are a Python loop and not a
    scan; autodiff differentiates it as it stands.

    Exact only where positions increase strictly along the sequence, as
    every caller's ``arange(S)`` does: the keys left out are then the ones
    the mask would have given weight zero.
    """
    outs = []
    with jax.named_scope("attn_causal_chunks"):
        for i in range(q.shape[1] // chunk):
            lo, hi = causal_key_range(i, chunk, window)
            qs = slice(i * chunk, hi)
            outs.append(_dense_attn(q[:, qs], k[:, lo:hi], v[:, lo:hi],
                                    positions[:, qs], positions[:, lo:hi],
                                    True, window))
        return jnp.concatenate(outs, axis=1)


def _chunked_attn(q, k, v, positions, causal, window, chunk_q):
    """Scan over query chunks: peak score temp is (B,KV,G,Qc,S) instead of
    (B,KV,G,S,S) — the XLA-path analogue of flash attention's tiling."""
    B, S, KV, hd = k.shape
    H = q.shape[2]
    G = H // KV
    nq = S // chunk_q
    qg = q.reshape(B, nq, chunk_q, KV, G, hd)
    qpos = positions.reshape(B, nq, chunk_q)

    def body(_, inp):
        qc, pc = inp                                        # (B,Qc,KV,G,hd)
        scores = jnp.einsum("bskgh,btkh->bkgst", qc, k) / math.sqrt(hd)
        mask = _mask(pc, positions, causal, window)         # (B, Qc, S)
        scores = jnp.where(mask[:, None, None],
                           scores.astype(jnp.float32), -1e9)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        o = jnp.einsum("bkgst,btkh->bskgh", w, v)
        return 0, o

    _, outs = jax.lax.scan(body, 0, (jnp.moveaxis(qg, 1, 0),
                                     jnp.moveaxis(qpos, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)


FLASH_HEAD_DIMS = (64, 128)


def attention_path(S, hd, causal, backend, *, mesh_devices=1,
                   chunk_q_threshold=8192, chunk_q=1024) -> str:
    """Which full-sequence path ``multihead_attn`` takes without
    ``use_flash``: "flash", "chunked", "causal_chunks" or "dense". The
    kernels mask a sliding window exactly as the XLA paths do, so the
    window does not enter the choice."""
    block = flash_ops.flash_block(S, hd)
    if (backend == "tpu" and causal and mesh_devices == 1
            and hd in FLASH_HEAD_DIMS and S % block == 0 and S >= 2 * block):
        return "flash"
    if S >= chunk_q_threshold and S % chunk_q == 0:
        return "chunked"
    if causal and S % CAUSAL_CHUNK == 0 and S >= 2 * CAUSAL_CHUNK:
        return "causal_chunks"
    return "dense"


def multihead_attn(p, x, positions, *, causal=True, window=0, rope_theta=1e4,
                   use_flash=False, chunk_q_threshold=8192, chunk_q=1024):
    """x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    H, hd = p["q"].shape[1], p["q"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["k"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"])
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    mesh = current_mesh()
    path = "flash" if use_flash else attention_path(
        S, hd, causal, jax.default_backend(),
        mesh_devices=mesh.size if mesh is not None else 1,
        chunk_q_threshold=chunk_q_threshold, chunk_q=chunk_q)
    if path == "flash":
        o = flash_ops.flash_attention(q, k, v, causal, window)
    elif path == "chunked":
        o = _chunked_attn(q, k, v, positions, causal, window, chunk_q)
    elif path == "causal_chunks":
        o = _causal_chunked_attn(q, k, v, positions, window, CAUSAL_CHUNK)
    else:
        o = _dense_attn(q, k, v, positions, positions, causal, window)
    return jnp.einsum("bshk,hkd->bsd", o, p["o"])


# --------------------------------------------------------------------------
# Decode with (rolling) KV cache
# --------------------------------------------------------------------------
class KVCache(NamedTuple):
    k: jax.Array          # (B, C, KV, hd)
    v: jax.Array          # (B, C, KV, hd)
    slot_pos: jax.Array   # (C,) int32, position stored in each slot (-1 empty)

    @staticmethod
    def init(batch, capacity, n_kv, head_dim, dtype):
        return KVCache(
            k=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
            v=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
            slot_pos=jnp.full((capacity,), -1, jnp.int32),
        )


def cache_capacity(seq_len: int, window: int) -> int:
    return min(seq_len, window) if window else seq_len


def decode_attn(p, x, cache: KVCache, pos, *, window=0, rope_theta=1e4):
    """x: (B, D) one new token at position ``pos`` (scalar int32).
    Returns (out (B, D), new_cache). Rolling write when window is set."""
    B, D = x.shape
    H, hd = p["q"].shape[1], p["q"].shape[2]
    KV = p["k"].shape[1]
    C = cache.k.shape[1]
    q = jnp.einsum("bd,dhk->bhk", x, p["q"])
    k = jnp.einsum("bd,dhk->bhk", x, p["k"])
    v = jnp.einsum("bd,dhk->bhk", x, p["v"])
    pos_b = jnp.broadcast_to(pos, (B, 1))
    q = apply_rope(q[:, None], pos_b, rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos_b, rope_theta)[:, 0]
    slot = jnp.where(window, pos % jnp.maximum(C, 1), pos).astype(jnp.int32)
    nk = jax.lax.dynamic_update_slice_in_dim(cache.k, k[:, None], slot, axis=1)
    nv = jax.lax.dynamic_update_slice_in_dim(cache.v, v[:, None], slot, axis=1)
    npos = cache.slot_pos.at[slot].set(pos.astype(jnp.int32))
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    scores = jnp.einsum("bkgh,bckh->bkgc", qg, nk) / math.sqrt(hd)
    valid = (npos >= 0) & (npos <= pos)
    if window:
        valid = valid & (npos > pos - window)
    scores = jnp.where(valid[None, None, None, :], scores.astype(jnp.float32), -1e9)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgc,bckh->bkgh", w, nv).reshape(B, H, hd)
    out = jnp.einsum("bhk,hkd->bd", o, p["o"])
    return out, KVCache(nk, nv, npos)
