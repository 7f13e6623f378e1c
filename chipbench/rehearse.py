"""Compile a configuration's programs for a described TPU v5e, without the
chip, and print what each needs of each device's memory.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py mamba2-2.7b --batch 1 2 4

For each batch: the program's train step, with the train state donated. Then
the reference's gradient of one row block, beside which the follower keeps
parameters, two moments, the summed gradient and the block's gradient, all
float32. A configuration with a ``layout`` is compiled over that mesh of a
described ``v5e:2x2``: the program's state, batch and step as the program
shards them, the reference's state spread by its own placement, and every
number per device. Without one, everything is on one described chip.
Nothing runs: these are compiles, not measurements. A device holds about
16.9e9 bytes.
"""
from __future__ import annotations

import argparse
import contextlib
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    sys.path.insert(0, str(p))

GB = 1e9


def _on(shardings, tree):
    """``tree``'s shapes, each leaf with its sharding from ``shardings``."""
    import jax
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), tree, shardings)


def _per_device(tree) -> int:
    """Bytes of ``tree``'s shards on its fullest device."""
    import jax
    return sum(math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--batch", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import model_under_test as mut
    from chipbench.reference.follow import spread
    from chipbench.spec import HERE, load_json
    from repro.launch.train import make_train_step
    from repro.models import Model
    from repro.optim import AdamWConfig, adamw_init

    jax.config.update("jax_enable_compilation_cache", False)
    conf = load_json(HERE / "configs" / f"{args.config}.json")
    ref = importlib.import_module(f"chipbench.reference.{conf['family']}")
    m = ref.dims(conf)
    seq = conf["train"]["seq"]
    described = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    cfg = mut.program_config(conf)
    model = Model(cfg)
    like = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), m,
        getattr(jnp, conf["program"]["fields"]["dtype"])))
    p32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       like)
    opt_like = jax.eval_shape(adamw_init, like)
    layout = conf.get("layout")
    if layout is None:
        one = SingleDeviceSharding(described[0])
        program, where = contextlib.nullcontext(), "one chip"
        everywhere = lambda tree: jax.tree.map(lambda _: one, tree)
        p_sh, o_sh, ref_sh = everywhere(like), everywhere(opt_like), \
            everywhere(p32)
        ref_whole = one
        batch_sh = lambda b: one
    else:
        mesh = mut.layout_mesh(layout, described)
        place = mut.Sharded(cfg, layout, mesh, args.batch[0])
        program = place.context()
        where = (f"mesh {dict(mesh.shape)} of a described v5e:2x2, "
                 f"{layout['strategy']}, per chip")
        p_sh, o_sh = place.params, place.opt
        ref_sh, ref_whole = spread(p32, list(mesh.devices.flat))
        batch_sh = lambda b: mut.Sharded(cfg, layout, mesh, b).batch

    with program:
        params, opt_state = _on(p_sh, like), _on(o_sh, opt_like)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"{args.config} on {where}: {n_params / 1e6:.1f}M parameters, "
              f"train state {_per_device((params, opt_state)) / GB:.2f} GB",
              flush=True)
        for b in args.batch:
            batch = {k: jax.ShapeDtypeStruct((b, seq), jnp.int32,
                                             sharding=batch_sh(b))
                     for k in ("tokens", "targets")}
            mem = make_train_step(
                model, AdamWConfig(**conf["train"]["optimizer"])) \
                .lower(params, opt_state, batch).compile().memory_analysis()
            print(f"  program step, batch {b} x {seq}: arguments "
                  f"{mem.argument_size_in_bytes / GB:.2f} GB, temporaries "
                  f"{mem.temp_size_in_bytes / GB:.2f} GB, aliased "
                  f"{mem.alias_size_in_bytes / GB:.2f} GB", flush=True)

    rb = conf["reference"]["row_block"]
    rows = jax.ShapeDtypeStruct((rb, seq), jnp.int32, sharding=ref_whole)
    p32 = _on(ref_sh, p32)
    with jax.default_matmul_precision("highest"):
        mem = jax.jit(jax.value_and_grad(
            lambda p, t, y: ref.loss(p, m, t, y)),
            out_shardings=(ref_whole, ref_sh)).lower(
            p32, rows, rows).compile().memory_analysis()
    p_bytes = _per_device(p32)
    print(f"  reference gradient, {rb} row(s): arguments "
          f"{mem.argument_size_in_bytes / GB:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / GB:.2f} GB, outputs "
          f"{mem.output_size_in_bytes / GB:.2f} GB; with the moments and "
          f"the summed gradient "
          f"{(4 * p_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes) / GB:.2f} GB",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
