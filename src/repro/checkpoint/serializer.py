"""Sharded pytree serialization.

Leaves are flattened with stable key paths, packed into N balanced shard
files of raw bytes, described by a manifest (written LAST -> atomic commit:
a checkpoint without a valid manifest does not exist). Restore validates
sizes and can re-shard onto any mesh (elastic restart, DESIGN.md §7).
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path

import jax
import numpy as np

from ..obs.spans import count, span


def flatten_with_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        out.append((key, leaf))
    return out


def plan_shards(leaves, n_shards: int):
    """Greedy size-balanced assignment: [(shard_idx, [(key, leaf), ...])]."""
    n_shards = max(1, n_shards)
    sizes = [0] * n_shards
    plan = [[] for _ in range(n_shards)]
    for key, leaf in sorted(leaves, key=lambda kl: -kl[1].nbytes):
        i = sizes.index(min(sizes))
        plan[i].append((key, leaf))
        sizes[i] += leaf.nbytes
    return plan


#: bytes of a leaf that is not C-contiguous copied at a time, through one
#: reused buffer per shard: a whole-leaf copy allocates and faults in fresh
#: memory for every byte, which slows the training thread's save while
#: eight writers do so at once
STAGE_BYTES = 8 << 20


def _c_order_blocks(arr, limit: int):
    """Consecutive pieces of ``arr`` of at most ``limit`` bytes whose
    C-order bytes, joined, are ``arr``'s: slices of its leading axis, or of
    each row where one row is larger than ``limit``."""
    if arr.nbytes <= limit:
        yield arr
        return
    rows = limit // (arr.nbytes // len(arr))
    if rows == 0:
        for row in arr:
            yield from _c_order_blocks(row, limit)
    else:
        for i in range(0, len(arr), rows):
            yield arr[i:i + rows]


def _timed_write(f, data) -> int:
    t0 = time.perf_counter_ns()
    f.write(data)
    return time.perf_counter_ns() - t0


def write_shard(path: Path, entries) -> dict:
    """Write one shard file; returns manifest fragment. fsync'd (the paper's
    experiments bypass page cache the same way). A C-contiguous leaf is
    written from its own host buffer as a flat byte view, so a writer makes
    no copy under the interpreter lock; a leaf that is not is copied into
    C order a block at a time through one reused buffer of
    ``STAGE_BYTES``. Traced as ``ckpt.shard``: ``bytes``, ``copied_bytes``
    (bytes of leaves that needed the copy), ``serialize_ns`` (getting each
    leaf's bytes: a view, or the copies), ``write_ns`` and ``fsync_ns``."""
    meta = {}
    offset = copied = serialize_ns = write_ns = 0
    stage = None
    with span("ckpt.shard"), open(path, "wb") as f:
        for key, arr in entries:
            arr = np.asarray(arr)
            if arr.flags.c_contiguous:
                t0 = time.perf_counter_ns()
                # reshape(-1) keeps 0-d scalars; a uint8 view exports a
                # buffer for dtypes the buffer protocol lacks (bfloat16)
                data = arr.reshape(-1).view(np.uint8)
                serialize_ns += time.perf_counter_ns() - t0
                write_ns += _timed_write(f, data)
            else:
                if stage is None:
                    stage = np.empty(STAGE_BYTES, np.uint8)
                for block in _c_order_blocks(arr, STAGE_BYTES):
                    t0 = time.perf_counter_ns()
                    data = stage[:block.nbytes]
                    np.copyto(data.view(arr.dtype).reshape(block.shape),
                              block)
                    serialize_ns += time.perf_counter_ns() - t0
                    write_ns += _timed_write(f, data)
                copied += arr.nbytes
            meta[key] = {"shape": list(arr.shape), "dtype": str(arr.dtype),
                         "offset": offset, "nbytes": arr.nbytes}
            offset += arr.nbytes
        t1 = time.perf_counter_ns()
        f.flush()
        with span("ckpt.shard.fsync"):
            os.fsync(f.fileno())
        fsync_ns = time.perf_counter_ns() - t1
        count("bytes", offset)
        count("copied_bytes", copied)
        count("serialize_ns", serialize_ns)
        count("write_ns", write_ns)
        count("fsync_ns", fsync_ns)
    return {"file": path.name, "entries": meta, "total_bytes": offset}


def _np_dtype(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # bf16 & friends (ships with jax)
        return np.dtype(getattr(ml_dtypes, name))


def read_shard(path: Path, frag: dict, out: dict) -> None:
    blob = path.read_bytes()
    if len(blob) != frag["total_bytes"]:
        raise IOError(f"shard {path} truncated: "
                      f"{len(blob)} != {frag['total_bytes']}")
    for key, m in frag["entries"].items():
        buf = blob[m["offset"]:m["offset"] + m["nbytes"]]
        out[key] = np.frombuffer(buf, dtype=_np_dtype(m["dtype"])) \
            .reshape(m["shape"])


def unflatten_like(tree, by_key: dict):
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for path, old in paths:
        key = jax.tree_util.keystr(path)
        if key not in by_key:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = by_key[key]
        if tuple(arr.shape) != tuple(old.shape):
            raise ValueError(
                f"shape mismatch for {key}: ckpt {arr.shape} vs {old.shape}")
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
