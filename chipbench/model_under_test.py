"""The benchmark's one door into the program's model code: the program's
configuration for a configuration file, and a check that the benchmark's
weights are laid out as the program stores them."""
from __future__ import annotations

import jax
import jax.numpy as jnp


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
