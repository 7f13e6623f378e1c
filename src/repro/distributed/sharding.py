"""Divisibility-aware logical-axis sharding (DESIGN.md §5).

Params/activations are annotated with *logical axis name* tuples; rules map
logical names to mesh axes. A rule is applied only when the dimension size is
divisible by the product of the mesh-axis sizes — otherwise the dim stays
replicated (this is what lets e.g. smollm's 15 heads lower cleanly on a
16-way "model" axis: its attention weights simply replicate).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes). None -> replicate.
LOGICAL_RULES: dict[str, object] = {
    "embed": "data",        # FSDP: weights stored sharded over data;
    #                         SPMD all-gathers one layer at a time inside scan
    "mlp": "model",         # TP
    "heads": "model",
    "kv_heads": "model",
    "vocab": "model",
    "experts": None,        # expert count (8/60) rarely divisible; TP via mlp
    "layers": None,
    "head_dim": None,
    "norm": None,
    "state": None,
    "conv": None,
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,
}


# Named sharding strategies (perf iterations, EXPERIMENTS.md §Perf).
# "tp_fsdp": TP over "model" + FSDP weight storage over "data" (default).
# "fsdp":    no tensor parallelism — batch shards over every mesh axis and
#            weights are fully sharded for storage with per-layer all-gather
#            (ZeRO-3). Kills all per-layer activation collectives; the right
#            regime whenever batch >= chips and the layer fits one chip.
STRATEGIES: dict[str, dict] = {
    "tp_fsdp": dict(LOGICAL_RULES),
    "fsdp": {**LOGICAL_RULES,
             "embed": ("data", "model"),
             "mlp": None, "heads": None, "kv_heads": None, "vocab": None,
             "batch": ("pod", "data", "model")},
    # dp_fsdp (perf iteration 4): no tensor parallelism; weights FSDP over
    # "data" only (replicated over "model" so XLA keeps grad reduction a
    # clean AR(model)+RS(data) instead of the in-loop full-grad ARs the
    # 2-D weight sharding provokes), batch over every axis, optimizer state
    # sharded 2-D separately (OPT_RULES).
    "dp_fsdp": {**LOGICAL_RULES,
                "embed": ("data",),
                "mlp": None, "heads": None, "kv_heads": None, "vocab": None,
                "batch": ("pod", "data", "model")},
    # tp_serve (perf iteration 5, decode cells): weight-stationary serving —
    # pure TP over "model", NO FSDP storage sharding, so a decode step never
    # all-gathers weights; batch over (pod, data); KV caches shard over
    # heads/batch. Right when the TP-sharded model fits chip memory.
    "tp_serve": {**LOGICAL_RULES, "embed": None},
    # dp_tp_moe (perf iteration 6, MoE trainers): dense parts pure-DP/FSDP
    # like dp_fsdp, but expert FFNs keep TP over "model" (the per-expert
    # d_ff shards) because expert weights dominate parameters and cannot
    # replicate; batch over (pod, data) only.
    "dp_tp_moe": {**LOGICAL_RULES,
                  "embed": ("data",), "heads": None, "kv_heads": None,
                  "vocab": None, "mlp": "model",
                  "batch": ("pod", "data")},
}

# optimizer-state rules per strategy (None -> same sharding as params)
OPT_RULES: dict[str, dict | None] = {
    "tp_fsdp": None,
    "fsdp": None,
    "dp_fsdp": {**LOGICAL_RULES,
                "embed": ("data", "model"), "mlp": ("model",),
                "heads": None, "kv_heads": None, "vocab": None},
}


class MeshContext(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict = dict(LOGICAL_RULES)


_ctx = MeshContext()


def current_mesh() -> Optional[Mesh]:
    return _ctx.mesh


def current_rules() -> dict:
    return _ctx.rules


@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: dict | None = None):
    prev_mesh, prev_rules = _ctx.mesh, _ctx.rules
    _ctx.mesh = mesh
    _ctx.rules = {**LOGICAL_RULES, **(rules or {})}
    try:
        # the plain Mesh context, not jax.set_mesh: under set_mesh, eager ops
        # re-express their outputs on the context mesh and fail for arrays
        # placed on another mesh (elastic restore compares both)
        with mesh:
            yield mesh
    finally:
        _ctx.mesh, _ctx.rules = prev_mesh, prev_rules


def _axis_size(mesh: Mesh, mesh_axes) -> int:
    if mesh_axes is None:
        return 1
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)
    n = 1
    for a in mesh_axes:
        n *= mesh.shape[a]
    return n


def divisible_prefix(dim: int, axes, mesh: Mesh, used=()) -> tuple:
    """Longest prefix of ``axes`` present in the mesh, unused, and whose
    size product divides ``dim`` (graceful degradation: batch=256 on a
    512-chip mesh shards over (pod, data) and replicates over model)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    axes = tuple(a for a in axes if a in mesh.shape and a not in used)
    while axes and dim % _axis_size(mesh, axes) != 0:
        axes = axes[:-1]
    return axes


def spec_for(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
             mesh: Mesh, rules: dict | None = None) -> P:
    """PartitionSpec for an array with the given logical axes, degrading any
    rule whose mesh-axis product does not divide the dimension to its
    longest divisible prefix, and never using a mesh axis twice."""
    rules = rules or current_rules()
    parts, used = [], set()
    for dim, name in zip(shape, logical_axes):
        mesh_axes = rules.get(name) if name else None
        tup = divisible_prefix(dim, mesh_axes, mesh, used)
        if not tup:
            parts.append(None)
            continue
        used.update(tup)
        parts.append(tup[0] if len(tup) == 1 else tup)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def logical_to_sharding(shape, logical_axes, mesh=None, rules=None):
    mesh = mesh or current_mesh()
    return NamedSharding(mesh, spec_for(shape, logical_axes, mesh, rules))


def shard_params(params, axes_tree, mesh=None, rules=None):
    """Tree of NamedShardings matching a params tree + logical-axes tree."""
    mesh = mesh or current_mesh()

    def f(leaf, axes):
        return logical_to_sharding(leaf.shape, axes, mesh, rules)
    # params is a structural prefix of axes_tree (its leaves are arrays where
    # axes_tree holds tuples of logical axis names), which tree.map allows.
    return jax.tree.map(f, params, axes_tree)


def shard_activation(x, logical_axes=None):
    """with_sharding_constraint for activations: batch dim over the batch
    rule, everything else replicated. No-op without a mesh context (CPU
    smoke tests). This anchors XLA's sharding propagation — without it the
    embedding table's layout leaks into the residual stream and the batch
    ends up replicated (perf iteration 3, EXPERIMENTS.md §Perf)."""
    mesh = current_mesh()
    if mesh is None or isinstance(mesh, jax.sharding.AbstractMesh):
        return x
    names = logical_axes or ("batch",) + (None,) * (x.ndim - 1)
    spec = spec_for(x.shape, names, mesh)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def batch_axes(mesh: Mesh | None = None, dim: int | None = None) -> tuple:
    """Mesh axes a global batch dimension shards over (strategy-aware; with
    ``dim`` given, degrades to the longest divisible prefix)."""
    mesh = mesh or current_mesh()
    ax = current_rules().get("batch") or ()
    if dim is None:
        ax = (ax,) if isinstance(ax, str) else tuple(ax)
        return tuple(a for a in ax if a in mesh.shape)
    return divisible_prefix(dim, ax, mesh)
