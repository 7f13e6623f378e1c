"""Mamba2 SSD chunked scan for TPU (pl.pallas_call + BlockSpec VMEM tiling).

Grid (b, nc, H/block_h): the chunk axis is sequential ("arbitrary") and the
(H, N, P) SSM state stays resident in VMEM across it — the inter-chunk
recurrence never touches HBM. The state lives in the h_last output block,
whose index depends on the batch row alone, so it is written back once per
row. Within a chunk every product is a 2-D matmul on the MXU, one head at a
time: C B^T is (Q, Q), the intra-chunk term (Q, Q) @ (Q, P), the state
read-out (Q, N) @ (N, P) and the state update (N, Q) @ (Q, P).

Layouts chosen for the TPU lowering: x and y are head-major, so a block's two
minor dims are (Q, P); per-head vectors (dt, the within-chunk cumulative
log-decay) come whole, (Q, H) and (H, Q), and one head's column or row is
selected with a one-hot mask and a lane/sublane sum (exact); the cumulative
sum itself and B^T are formed by the wrapper. D is read from SMEM.

TPU adaptation: the original SSD CUDA kernel leans on warp-wide segsum
primitives; here the segment-sum is a broadcasted subtraction of the
wrapper's cumulative sums, and state passing is VMEM-resident rather than
shared-memory tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(lcum_ref, lcum_t_ref, dt_ref, x_ref, b_ref, bt_ref, c_ref,
                d_ref, y_ref, h_ref, *, block_h: int, chunk: int):
    ci = pl.program_id(1)
    hi = pl.program_id(2)

    @pl.when(jnp.logical_and(ci == 0, hi == 0))
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    lcum_all = lcum_ref[...]                       # (Q, H)
    lcum_t_all = lcum_t_ref[...]                   # (H, Q)
    dt_all = dt_ref[...]                           # (Q, H)
    n_heads = lcum_all.shape[1]
    bm = b_ref[...]                                # (Q, N)
    bt = bt_ref[...]                               # (N, Q)
    cm = c_ref[...]                                # (Q, N)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, n_heads), 1)
    sub_head = jax.lax.broadcasted_iota(jnp.int32, (n_heads, 1), 0)

    def head_step(j, carry):
        head = hi * block_h + j
        # one head's per-step vectors as a column (Q, 1) and a row (1, Q)
        lc = jnp.sum(jnp.where(lane_head == head, lcum_all, 0.0),
                     axis=1, keepdims=True)
        dt = jnp.sum(jnp.where(lane_head == head, dt_all, 0.0),
                     axis=1, keepdims=True)
        lc_row = jnp.sum(jnp.where(sub_head == head, lcum_t_all, 0.0),
                         axis=0, keepdims=True)
        lc_end = lc_row[:, chunk - 1:]             # (1, 1)
        x = x_ref[j].astype(jnp.float32)           # (Q, P)
        xdt = x * dt
        L = jnp.where(jj <= ii, jnp.exp(lc - lc_row), 0.0)   # (Q, Q)
        y = jnp.dot(cb * L, xdt, preferred_element_type=jnp.float32)
        h = h_ref[head]                            # (N, P)
        y = y + jnp.dot(cm, h, preferred_element_type=jnp.float32) * jnp.exp(lc)
        s_c = jnp.dot(bt, xdt * jnp.exp(lc_end - lc),
                      preferred_element_type=jnp.float32)    # (N, P)
        h_ref[head] = h * jnp.exp(lc_end) + s_c
        y_ref[j] = (y + d_ref[head] * x).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_h, head_step, 0)


def ssd_scan_fwd(x, dt, B, C, la, D, *, block_h: int = 0, interpret=False):
    """x (b,nc,Q,H,P); dt,la (b,nc,Q,H); B,C (b,nc,Q,N); D (H,).
    Returns (y (b, nc*Q, H, P), h_last (b, H, N, P))."""
    b, nc, Q, H, P = x.shape
    N = B.shape[-1]
    block_h = block_h or min(H, 8)
    assert H % block_h == 0
    nh = H // block_h
    f32 = jnp.float32
    lcum = jnp.cumsum(la.astype(f32), axis=2)      # (b, nc, Q, H)
    kern = functools.partial(_ssd_kernel, block_h=block_h, chunk=Q)
    per_chunk = lambda *tail: pl.BlockSpec(
        (None, None) + tail, lambda bi, ci, hi: (bi, ci) + (0,) * len(tail))
    y, h_last = pl.pallas_call(
        kern,
        grid=(b, nc, nh),
        in_specs=[
            per_chunk(Q, H),                       # lcum
            per_chunk(H, Q),                       # lcum^T
            per_chunk(Q, H),                       # dt
            pl.BlockSpec((None, None, block_h, Q, P),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            per_chunk(Q, N),                       # B
            per_chunk(N, Q),                       # B^T
            per_chunk(Q, N),                       # C
            pl.BlockSpec(memory_space=pltpu.SMEM),  # D
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_h, Q, P),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((None, H, N, P), lambda bi, ci, hi: (bi, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, H, Q, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, N, P), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(lcum, jnp.swapaxes(lcum, 2, 3), dt.astype(f32), jnp.swapaxes(x, 2, 3),
      B.astype(f32), jnp.swapaxes(B, 2, 3).astype(f32), C.astype(f32),
      D.astype(f32))
    return jnp.swapaxes(y, 2, 3).reshape(b, nc * Q, H, P), h_last
