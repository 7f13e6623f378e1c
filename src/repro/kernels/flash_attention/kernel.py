"""Flash attention for TPU (pl.pallas_call + explicit BlockSpec VMEM tiling).

Online-softmax tiling: grid (B, H, nq, nk); the nk axis is sequential
("arbitrary") and carries running max / denominator / accumulator in VMEM
scratch. The wrapper lays q/k/v out head-major, (B, H, S, hd), so every
block's two minor dims are (block, hd): a sublane-aligned row count by the
whole head dim, which is what the TPU lowering accepts. Running max and
denominator are kept lane-replicated as (block_q, 128) so their scratch is a
whole number of vreg tiles. Causal and sliding-window masks are applied
blockwise; fully-masked blocks short-circuit via pl.when.

TPU adaptation notes: the CUDA flash algorithm's warp-level shuffles have no
TPU analogue — the TPU-native formulation keeps the (block_q, head_dim)
accumulator resident in VMEM across the sequential nk grid dimension and lets
the MXU do the (block_q x hd) @ (hd x block_k) products; masking is
vectorised on the VPU with 2-D iotas.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, nk: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level reachability: skip fully-masked tiles
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window:
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window) \
            if causal else (k_start + block_k - 1 > q_start - window)

    @pl.when(reachable if (causal or window) else True)
    def _compute():
        q = q_ref[...]                                   # (bq, hd)
        k = k_ref[...]                                   # (bk, hd)
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]                            # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=0,
                        block_q=512, block_k=512, interpret=False):
    """q: (B,S,H,hd) bf16/f32; k, v: (B,S,KV,hd). Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_k)
    qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))  # (B, heads, S, hd)
    kern = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, block_q=block_q, block_k=block_k, nk=nk, seq_len=S)
    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, hd),
                           lambda b, h, qi, ki: (b, h // G, ki, 0))
    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return jnp.swapaxes(out, 1, 2)
