"""Flash attention for TPU: a forward kernel and two backward kernels
(pl.pallas_call + explicit BlockSpec VMEM tiling). The (Sq, Sk) scores live
in VMEM one block at a time and never reach HBM.

Layout. The wrappers lay q/k/v out head-major, (B, heads, S, hd), so every
block's two minor dims are (block, hd): a sublane-aligned row count by the
whole head dim, which is what the TPU lowering accepts. The ``hg`` query
heads of one grid step share one KV head; their blocks are folded into the
rows of one (hg * bq, hd) operand, so K/V are fetched once for all of them
and GQA never expands K/V to H heads in HBM. Per-row statistics (the
log-sum-exp, and D = rowsum(dO * O)) travel between kernels as lane-major
rows of ``hg * bq`` values, (B, H // hg, nq, 1, hg * bq); ``_to_groups`` and
``_from_groups`` convert from and to (B, H, S).

Block pairs. Every kernel walks a table of the (q block, k block) pairs that
some unmasked (query, key) pair lies in, handed to it as scalar prefetch: a
block that the causal or sliding-window mask hides wholly is neither fetched
nor computed, and takes no grid step. Each pair's flags say whether it
starts or ends its accumulation and whether it straddles the mask's edge;
only straddling blocks build and apply the mask.

- forward: grid (B, H // hg, pairs by q block); online softmax with running
  max, denominator and accumulator in VMEM scratch; writes O and the
  log-sum-exp.
- dQ: grid (B, H // hg, pairs by q block); recomputes P from Q, K and the
  log-sum-exp; dQ accumulates in VMEM.
- dK/dV: grid (B, KV, pairs by k block over the G // hg head groups of that
  KV head); works on transposed scores (bk, hg * bq), so the row statistics
  broadcast over sublanes; dK and dV accumulate in VMEM over every query
  head of the KV head.

``mxu_dtype`` is the dtype of every matmul's operands; products accumulate
in float32, as do the softmax statistics and the gradient accumulators.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# Query heads folded into one grid step: the largest divisor of G for which
# the step's score tile, hg * bq * bk elements, stays within this.
MAX_SCORE_TILE = 1024 * 1024

FIRST, LAST, EDGE = 1, 2, 4           # block-pair flags
_NT = (((1,), (1,)), ((), ()))         # a @ b.T
_NN = (((1,), (0,)), ((), ()))         # a @ b


def _heads_per_step(G: int, bq: int, bk: int) -> int:
    return max(d for d in range(1, G + 1)
               if G % d == 0 and (d == 1 or d * bq * bk <= MAX_SCORE_TILE))


def _block_masks(s_pad, seq_len, bq, bk, causal, window):
    """(nq, nk) booleans: which block pairs hold an unmasked (query, key)
    pair, and which of those also hold a masked one. A pair is unmasked
    where lo <= q - k <= hi and k < seq_len; over a block, q - k takes every
    value between its extremes, so the tests are interval overlaps."""
    q0 = np.arange(s_pad // bq)[:, None] * bq
    k0 = np.arange(s_pad // bk)[None, :] * bk
    dmin, dmax = q0 - (k0 + bk - 1), (q0 + bq - 1) - k0
    lo = 0 if causal else -s_pad
    hi = window - 1 if window else s_pad
    visible = (dmax >= lo) & (dmin <= hi) & (k0 < seq_len)
    inside = (dmin >= lo) & (dmax <= hi) & (k0 + bk <= seq_len)
    return visible, visible & ~inside


def _flags(edge, first, last):
    return (np.where(first, FIRST, 0) | np.where(last, LAST, 0)
            | np.where(edge, EDGE, 0)).astype(np.int32)


def _q_major_pairs(s_pad, seq_len, bq, bk, causal, window):
    """(qi, ki, flags) int32 tables, by q block then k block."""
    visible, edge = _block_masks(s_pad, seq_len, bq, bk, causal, window)
    qi, ki = np.nonzero(visible)                 # row-major: qi, then ki
    first = np.r_[True, qi[1:] != qi[:-1]]
    last = np.r_[qi[1:] != qi[:-1], True]
    return (qi.astype(np.int32), ki.astype(np.int32),
            _flags(edge[qi, ki], first, last))


def _k_major_pairs(s_pad, seq_len, bq, bk, causal, window, n_groups):
    """(ki, group, qi, flags) int32 tables, by k block, then head group,
    then q block."""
    visible, edge = _block_masks(s_pad, seq_len, bq, bk, causal, window)
    rows = [(k, g, q) for k in range(visible.shape[1])
            for g in range(n_groups) for q in np.nonzero(visible[:, k])[0]]
    ki, gi, qi = (np.array(c, np.int32) for c in zip(*rows))
    first = np.r_[True, ki[1:] != ki[:-1]]
    last = np.r_[ki[1:] != ki[:-1], True]
    return ki, gi, qi, _flags(edge[qi, ki], first, last)


def _to_groups(x, hg, bq):
    """(B, H, S) -> (B, H // hg, S // bq, 1, hg * bq): each q block's rows of
    ``hg`` heads, head-major, as one lane-major row."""
    B, H, S = x.shape
    x = x.reshape(B, H // hg, hg, S // bq, bq).transpose(0, 1, 3, 2, 4)
    return x.reshape(B, H // hg, S // bq, 1, hg * bq)


def _from_groups(x, hg, bq):
    B, NG, nq, _, n = x.shape
    x = x.reshape(B, NG, nq, hg, bq).transpose(0, 1, 3, 2, 4)
    return x.reshape(B, NG * hg, nq * bq)


def _allowed(qpos, kpos, causal, window, seq_len, s_pad):
    ok = kpos <= qpos if causal else None
    if window:
        w = kpos > qpos - window
        ok = w if ok is None else ok & w
    if seq_len < s_pad:
        p = kpos < seq_len
        ok = p if ok is None else ok & p
    return ok


def _lanes(x, width):
    """A lane-replicated (n, 128) column statistic as an (n, width) operand."""
    if width % LANES == 0:
        return pltpu.repeat(x, width // LANES, axis=1)
    return x[:, :1]


def _column(row):
    """A (1, n) row as a lane-replicated (n, 128) column."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _by_edge(flags, edges, step):
    """Run ``step(edge)`` for the pair's kind; ``edges`` holds the kinds
    the table has, so a kernel without edge blocks builds no mask."""
    if len(edges) == 1:
        step(*edges)
        return
    pl.when((flags & EDGE) != 0)(functools.partial(step, True))
    pl.when((flags & EDGE) == 0)(functools.partial(step, False))


def _edges(flags):
    return tuple(sorted({bool(f & EDGE) for f in flags.tolist()}))


def _dot(a, b, dims, mxu):
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), dims,
                               preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------
def _fwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale, mask, edges, hg, bq, bk, mxu):
    t = pl.program_id(2)
    flags = fl_ref[t]
    n, hd = hg * bq, q_ref.shape[-1]

    @pl.when((flags & FIRST) != 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(edge):
        q = q_ref[...].reshape(n, hd)
        s = _dot(q, k_ref[...], _NT, mxu) * scale           # (n, bk)
        if edge:
            row = jax.lax.broadcasted_iota(jnp.int32, (n, bk), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (n, bk), 1)
            ok = mask(qi_ref[t] * bq + row % bq, ki_ref[t] * bk + col)
            s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev = m_sc[...], l_sc[...]                # (n, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        if edge:          # rows with no unmasked key yet have m_new NEG_INF
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * _lanes(alpha, hd) \
            + _dot(p, v_ref[...], _NN, mxu)

    _by_edge(flags, edges, step)

    @pl.when((flags & LAST) != 0)
    def _finalize():
        l = l_sc[...]
        l = jnp.where(l == 0.0, 1.0, l)                      # fully masked row
        o = acc_sc[...] / _lanes(l, hd)
        o_ref[...] = o.reshape(hg, bq, hd).astype(o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]        # (1, n)


def _grid_call(kernel, grid, tables, in_specs, out_specs, out_shape, scratch,
               name, interpret, args):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*(jnp.asarray(t) for t in tables), *args)


def _geometry(q, k, bq, bk):
    B, H, S, hd = q.shape
    G = H // k.shape[1]
    hg = _heads_per_step(G, bq, bk)
    return B, H, S, hd, G, hg


def flash_attention_fwd(q, k, v, *, causal=True, window=0, block_q=512,
                        block_k=512, interpret=False, mxu_dtype=None):
    """q: (B,S,H,hd) bf16/f32; k, v: (B,S,KV,hd). Returns the output,
    (B,S,H,hd), and the float32 log-sum-exp of each row, (B,H,S)."""
    S = q.shape[1]
    bq, bk = min(block_q, S), min(block_k, S)
    qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    qh, kh, vh, s_pad = _pad((qh, kh, vh), math.lcm(bq, bk))
    o, lse = _fwd_head_major(qh, kh, vh, S, causal=causal, window=window,
                             bq=bq, bk=bk, interpret=interpret,
                             mxu=mxu_dtype or q.dtype)
    return jnp.swapaxes(o[:, :, :S], 1, 2), lse[:, :, :S]


def _pad(arrays, block):
    S = arrays[0].shape[2]
    s_pad = -(-S // block) * block
    if s_pad == S:
        return (*arrays, s_pad)
    pad = ((0, 0), (0, 0), (0, s_pad - S), (0, 0))
    return (*(jnp.pad(a, pad) for a in arrays), s_pad)


def _fwd_head_major(qh, kh, vh, seq_len, *, causal, window, bq, bk,
                    interpret, mxu):
    B, H, S, hd, G, hg = _geometry(qh, kh, bq, bk)
    n, ng = hg * bq, G // hg
    tables = _q_major_pairs(S, seq_len, bq, bk, causal, window)
    mask = functools.partial(_allowed, causal=causal, window=window,
                             seq_len=seq_len, s_pad=S)
    kern = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(hd),
                             mask=mask, edges=_edges(tables[-1]), hg=hg,
                             bq=bq, bk=bk, mxu=mxu)
    q_spec = pl.BlockSpec((None, hg, bq, hd),
                          lambda b, g, t, qi, ki, fl: (b, g, qi[t], 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, g, t, qi, ki, fl: (b, g // ng, ki[t], 0))
    row_spec = pl.BlockSpec((None, None, None, 1, n),
                            lambda b, g, t, qi, ki, fl: (b, g, qi[t], 0, 0))
    o, lse = _grid_call(
        kern, (B, H // hg, len(tables[0])), tables,
        [q_spec, kv_spec, kv_spec], [q_spec, row_spec],
        [jax.ShapeDtypeStruct(qh.shape, qh.dtype),
         jax.ShapeDtypeStruct((B, H // hg, S // bq, 1, n), jnp.float32)],
        [pltpu.VMEM((n, LANES), jnp.float32),    # running max
         pltpu.VMEM((n, LANES), jnp.float32),    # running denominator
         pltpu.VMEM((n, hd), jnp.float32)],      # output accumulator
        "flash_attn_fwd", interpret, (qh, kh, vh))
    return o, _from_groups(lse, hg, bq)


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------
def _dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               d_ref, dq_ref, lse_sc, d_sc, acc_sc, *, scale, mask, edges, hg,
               bq, bk, mxu):
    t = pl.program_id(2)
    flags = fl_ref[t]
    n, hd = hg * bq, q_ref.shape[-1]

    @pl.when((flags & FIRST) != 0)
    def _init():
        lse_sc[...] = _column(lse_ref[...])
        d_sc[...] = _column(d_ref[...])
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(edge):
        k = k_ref[...]
        s = _dot(q_ref[...].reshape(n, hd), k, _NT, mxu) * scale   # (n, bk)
        p = jnp.exp(s - _lanes(lse_sc[...], bk))
        if edge:
            row = jax.lax.broadcasted_iota(jnp.int32, (n, bk), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (n, bk), 1)
            ok = mask(qi_ref[t] * bq + row % bq, ki_ref[t] * bk + col)
            p = jnp.where(ok, p, 0.0)
        dp = _dot(do_ref[...].reshape(n, hd), v_ref[...], _NT, mxu)
        ds = p * (dp - _lanes(d_sc[...], bk))
        acc_sc[...] += _dot(ds, k, _NN, mxu)

    _by_edge(flags, edges, step)

    @pl.when((flags & LAST) != 0)
    def _finalize():
        dq_ref[...] = (acc_sc[...] * scale).reshape(hg, bq, hd) \
            .astype(dq_ref.dtype)


def _dkdv_kernel(ki_ref, gi_ref, qi_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                 lse_ref, d_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, mask,
                 edges, hg, bq, bk, mxu):
    t = pl.program_id(2)
    flags = fl_ref[t]
    n, hd = hg * bq, q_ref.shape[-1]

    @pl.when((flags & FIRST) != 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(edge):
        q = q_ref[...].reshape(n, hd)
        do = do_ref[...].reshape(n, hd)
        st = _dot(k_ref[...], q, _NT, mxu) * scale              # (bk, n)
        pt = jnp.exp(st - lse_ref[...])
        if edge:
            row = jax.lax.broadcasted_iota(jnp.int32, (bk, n), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (bk, n), 1)
            ok = mask(qi_ref[t] * bq + col % bq, ki_ref[t] * bk + row)
            pt = jnp.where(ok, pt, 0.0)
        dv_sc[...] += _dot(pt, do, _NN, mxu)
        dpt = _dot(v_ref[...], do, _NT, mxu)                    # (bk, n)
        dst = pt * (dpt - d_ref[...])
        dk_sc[...] += _dot(dst, q, _NN, mxu)

    _by_edge(flags, edges, step)

    @pl.when((flags & LAST) != 0)
    def _finalize():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=0,
                        dq_blocks=(512, 512), dkdv_blocks=(512, 512),
                        interpret=False, mxu_dtype=None):
    """Gradients of ``flash_attention_fwd`` with respect to q, k, v, from
    its output ``o``, its log-sum-exp ``lse`` (B,H,S) and the output's
    cotangent ``do``. Shapes as the forward's; returns (dq, dk, dv)."""
    S = q.shape[1]
    mxu = mxu_dtype or q.dtype
    dq_b = tuple(min(b, S) for b in dq_blocks)
    dkdv_b = tuple(min(b, S) for b in dkdv_blocks)
    qh, kh, vh, oh, doh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v, o, do))
    # D = rowsum(dO * O), once per row, in float32. dO is rounded as the
    # kernels' dP = dO V^T rounds it, so that dP - D cancels the rounding.
    d = jnp.sum(doh.astype(mxu).astype(jnp.float32) * oh.astype(jnp.float32),
                axis=-1)
    qh, kh, vh, doh, s_pad = _pad((qh, kh, vh, doh),
                                  math.lcm(*dq_b, *dkdv_b))
    lse, d = (jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - S))) for x in (lse, d))
    kw = dict(seq_len=S, causal=causal, window=window, interpret=interpret,
              mxu=mxu)
    dq = _dq_head_major(qh, kh, vh, doh, lse, d, *dq_b, **kw)
    dk, dv = _dkdv_head_major(qh, kh, vh, doh, lse, d, *dkdv_b, **kw)
    return tuple(jnp.swapaxes(x[:, :, :S], 1, 2) for x in (dq, dk, dv))


def _dq_head_major(qh, kh, vh, doh, lse, d, bq, bk, *, seq_len, causal,
                   window, interpret, mxu):
    B, H, S, hd, G, hg = _geometry(qh, kh, bq, bk)
    n, ng = hg * bq, G // hg
    tables = _q_major_pairs(S, seq_len, bq, bk, causal, window)
    mask = functools.partial(_allowed, causal=causal, window=window,
                             seq_len=seq_len, s_pad=S)
    kern = functools.partial(_dq_kernel, scale=1.0 / math.sqrt(hd),
                             mask=mask, edges=_edges(tables[-1]), hg=hg,
                             bq=bq, bk=bk, mxu=mxu)
    q_spec = pl.BlockSpec((None, hg, bq, hd),
                          lambda b, g, t, qi, ki, fl: (b, g, qi[t], 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, g, t, qi, ki, fl: (b, g // ng, ki[t], 0))
    row_spec = pl.BlockSpec((None, None, None, 1, n),
                            lambda b, g, t, qi, ki, fl: (b, g, qi[t], 0, 0))
    return _grid_call(
        kern, (B, H // hg, len(tables[0])), tables,
        [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec], q_spec,
        jax.ShapeDtypeStruct(qh.shape, qh.dtype),
        [pltpu.VMEM((n, LANES), jnp.float32),    # log-sum-exp, as a column
         pltpu.VMEM((n, LANES), jnp.float32),    # D, as a column
         pltpu.VMEM((n, hd), jnp.float32)],      # dQ accumulator
        "flash_attn_dq", interpret,
        (qh, kh, vh, doh, _to_groups(lse, hg, bq), _to_groups(d, hg, bq)))


def _dkdv_head_major(qh, kh, vh, doh, lse, d, bq, bk, *, seq_len, causal,
                     window, interpret, mxu):
    B, H, S, hd, G, hg = _geometry(qh, kh, bq, bk)
    KV, n, ng = kh.shape[1], hg * bq, G // hg
    tables = _k_major_pairs(S, seq_len, bq, bk, causal, window, ng)
    mask = functools.partial(_allowed, causal=causal, window=window,
                             seq_len=seq_len, s_pad=S)
    kern = functools.partial(_dkdv_kernel, scale=1.0 / math.sqrt(hd),
                             mask=mask, edges=_edges(tables[-1]), hg=hg,
                             bq=bq, bk=bk, mxu=mxu)
    q_spec = pl.BlockSpec(
        (None, hg, bq, hd),
        lambda b, j, t, ki, gi, qi, fl: (b, j * ng + gi[t], qi[t], 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, j, t, ki, gi, qi, fl: (b, j, ki[t], 0))
    row_spec = pl.BlockSpec(
        (None, None, None, 1, n),
        lambda b, j, t, ki, gi, qi, fl: (b, j * ng + gi[t], qi[t], 0, 0))
    return _grid_call(
        kern, (B, KV, len(tables[0])), tables,
        [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        [kv_spec, kv_spec],
        [jax.ShapeDtypeStruct(kh.shape, kh.dtype),
         jax.ShapeDtypeStruct(vh.shape, vh.dtype)],
        [pltpu.VMEM((bk, hd), jnp.float32),      # dK accumulator
         pltpu.VMEM((bk, hd), jnp.float32)],     # dV accumulator
        "flash_attn_dkdv", interpret,
        (qh, kh, vh, doh, _to_groups(lse, hg, bq), _to_groups(d, hg, bq)))
