"""Mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state; dryrun.py sets XLA_FLAGS before importing anything.

Every mesh is built with ``Auto`` axis types: the model code places arrays
with NamedShardings and sharding constraints and leaves propagation to XLA.
JAX 0.9's default (``Explicit``) axes would instead demand an output sharding
at every gather, e.g. the embedding lookup.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axis types on every axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """(n, 1) mesh on whatever devices exist — smoke tests / CPU runs."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
