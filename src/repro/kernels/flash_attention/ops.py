"""jit'd public wrapper for the flash-attention kernels.

``flash_attention`` is a custom_vjp: the forward kernel returns the output
and each row's log-sum-exp, and the backward runs the dQ and dK/dV kernels
on them (``kernel.py``), so training through it never builds the (Sq, Sk)
scores in HBM. On the CPU the kernels run in interpret mode.

Matmul operands are what XLA's default precision gives float32 on each
backend: one bfloat16 pass on the TPU, float32 in interpret mode on the CPU;
bfloat16 inputs stay bfloat16. Accumulation is float32 throughout.

Block sizes come from the shape (``blocks_for``) unless the caller gives
them, in which case all three kernels use them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bwd, flash_attention_fwd

# (bq, bk) of each kernel at S >= 1024. Not yet timed on a chip: at
# SmolLM-360M's training shape (B 4, S 2048, H 15, KV 5, hd 64) 512 x 512
# takes the fewest grid steps of the blocks that tile, for 62.5% of S x S
# computed against 56% at 256.
BLOCKS = {"fwd": (512, 512), "dq": (512, 512), "dkdv": (512, 512)}


def blocks_for(S: int, hd: int) -> dict:
    """Each kernel's (bq, bk) at sequence length S: the tuned blocks, halved
    until S holds at least two of them (never below 128)."""
    del hd  # every tuned block fits VMEM at hd 64 and 128
    cap = max(128, 1 << max(0, (S // 2).bit_length() - 1))
    return {name: tuple(min(b, cap) for b in bqk)
            for name, bqk in BLOCKS.items()}


def flash_block(S: int, hd: int) -> int:
    """The block S must be a multiple of for the kernels to tile it."""
    return max(b for bqk in blocks_for(S, hd).values() for b in bqk)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _mxu_dtype(dtype):
    return jnp.bfloat16 if dtype == jnp.float32 and not _on_cpu() else dtype


def _blocks(q, block_q, block_k):
    if block_q is None:
        return blocks_for(q.shape[1], q.shape[3])
    return dict.fromkeys(("fwd", "dq", "dkdv"), (block_q, block_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=0, block_q=None,
                    block_k=None):
    return _fwd(q, k, v, causal, window, block_q, block_k)[0]


def _fwd(q, k, v, causal, window, block_q, block_k):
    bq, bk = _blocks(q, block_q, block_k)["fwd"]
    with jax.named_scope("attn_flash"):
        out, lse = flash_attention_fwd(
            q, k, v, causal=causal, window=window, block_q=bq, block_k=bk,
            interpret=_on_cpu(), mxu_dtype=_mxu_dtype(q.dtype))
    return out, (q, k, v, out, lse)


def _bwd(causal, window, block_q, block_k, res, g):
    q, k, v, out, lse = res
    blocks = _blocks(q, block_q, block_k)
    with jax.named_scope("attn_flash"):
        return flash_attention_bwd(
            q, k, v, out, lse, g, causal=causal, window=window,
            dq_blocks=blocks["dq"], dkdv_blocks=blocks["dkdv"],
            interpret=_on_cpu(), mxu_dtype=_mxu_dtype(q.dtype))


flash_attention.defvjp(_fwd, _bwd)
