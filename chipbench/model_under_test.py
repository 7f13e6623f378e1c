"""The benchmark's one door into the program's model code: the program's
configuration for a configuration file, a check that the benchmark's
weights are laid out as the program stores them, and the program's own
placement of a train state over the mesh a configuration's ``layout``
states."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def program_config(conf: dict):
    """The program's ModelConfig, with every field the file states."""
    from repro.configs import get_config
    fields = dict(conf["program"]["fields"])
    fields["dtype"] = getattr(jnp, fields["dtype"])
    return get_config(conf["program"]["arch"]).replace(**fields)


def check_layout(cfg, params) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of the
    program's own parameters for ``cfg``."""
    from repro.models import Model
    want = jax.eval_shape(lambda: Model(cfg).init(jax.random.PRNGKey(0))[0])
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise RuntimeError(f"weight tree differs from the program's: "
                           f"{jax.tree.structure(params)} vs "
                           f"{jax.tree.structure(want)}")
    for (path, w), p in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(params)):
        if w.shape != p.shape or w.dtype != p.dtype:
            raise RuntimeError(f"{jax.tree_util.keystr(path)}: "
                               f"{p.shape} {p.dtype} vs the program's "
                               f"{w.shape} {w.dtype}")


def layout_mesh(layout: dict, devices=None):
    """The mesh ``layout["mesh"]`` states ({axis: size}, in the program's
    axis names), with the axis types the program's own ``make_mesh``
    gives: over the first devices JAX finds, or over ``devices`` (a
    described chip's, for a compile without it)."""
    from jax.sharding import AxisType, Mesh
    from repro.launch.mesh import make_mesh
    shape, axes = tuple(layout["mesh"].values()), tuple(layout["mesh"])
    if devices is None:
        return make_mesh(shape, axes)
    grid = np.asarray(devices[:math.prod(shape)]).reshape(shape)
    return Mesh(grid, axes, axis_types=(AxisType.Auto,) * len(axes))


class Sharded:
    """Where the program puts a train state and its batches over ``mesh``
    under the strategy ``layout["strategy"]`` names (a key of the program's
    ``STRATEGIES``): parameters by their logical axes, AdamW's moments as
    the strategy's optimizer rules say (as the parameters where it has
    none), the step count replicated, a global batch of ``batch`` rows over
    the strategy's batch axes. Trace the program inside ``context()``."""

    def __init__(self, cfg, layout: dict, mesh, batch: int):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import (OPT_RULES, STRATEGIES,
                                                batch_axes, spec_for)
        from repro.launch.specs import model_shapes_and_axes, tree_shardings
        from repro.models import Model
        from repro.optim import AdamWState
        self.mesh = mesh
        self.rules = STRATEGIES[layout["strategy"]]
        opt_rules = OPT_RULES.get(layout["strategy"])
        with self.context():
            shapes, axes = model_shapes_and_axes(Model(cfg))
            self.params = tree_shardings(shapes, axes, mesh)
            moments = self.params if opt_rules is None else jax.tree.map(
                lambda s, ax: NamedSharding(
                    mesh, spec_for(s.shape, ax, mesh, opt_rules)),
                shapes, axes)
            self.opt = AdamWState(moments, moments, NamedSharding(mesh, P()))
            rows = batch_axes(mesh, batch)
            self.batch = NamedSharding(mesh, P(rows or None))

    def context(self):
        from repro.distributed import mesh_context
        return mesh_context(self.mesh, rules=self.rules)
