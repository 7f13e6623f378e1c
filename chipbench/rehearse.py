"""Compile a configuration's programs for a described TPU v5e chip, without
the chip, and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py mamba2-2.7b --batch 1 2 4

For each batch: the program's train step, with the train state donated. Then
the reference's gradient of one row block, beside which the follower keeps
parameters, two moments, the summed gradient and the block's gradient, all
float32. Nothing runs: these are compiles, not measurements. The device
holds about 16.9e9 bytes.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    sys.path.insert(0, str(p))

GB = 1e9


def _on(sharding, tree):
    import jax
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


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
    from chipbench.spec import HERE, load_json
    from repro.launch.train import make_train_step
    from repro.models import Model
    from repro.optim import AdamWConfig, adamw_init

    jax.config.update("jax_enable_compilation_cache", False)
    conf = load_json(HERE / "configs" / f"{args.config}.json")
    ref = importlib.import_module(f"chipbench.reference.{conf['family']}")
    m = ref.dims(conf)
    seq = conf["train"]["seq"]
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cfg = mut.program_config(conf)
    model = Model(cfg)
    params = _on(one, jax.eval_shape(
        lambda: ref.init_params(jax.random.PRNGKey(0), m,
                                getattr(jnp, conf["program"]["fields"]["dtype"]))))
    opt_state = _on(one, jax.eval_shape(adamw_init, params))
    state = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves((params, opt_state)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{args.config}: {n_params / 1e6:.1f}M parameters, train state "
          f"{state / GB:.2f} GB", flush=True)
    for b in args.batch:
        batch = {k: jax.ShapeDtypeStruct((b, seq), jnp.int32, sharding=one)
                 for k in ("tokens", "targets")}
        mem = make_train_step(model, AdamWConfig(**conf["train"]["optimizer"])) \
            .lower(params, opt_state, batch).compile().memory_analysis()
        print(f"  program step, batch {b} x {seq}: arguments "
              f"{mem.argument_size_in_bytes / GB:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / GB:.2f} GB, aliased "
              f"{mem.alias_size_in_bytes / GB:.2f} GB", flush=True)

    rb = conf["reference"]["row_block"]
    rows = jax.ShapeDtypeStruct((rb, seq), jnp.int32, sharding=one)
    p32 = _on(one, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params))
    with jax.default_matmul_precision("highest"):
        mem = jax.jit(jax.value_and_grad(
            lambda p, t, y: ref.loss(p, m, t, y))).lower(
            p32, rows, rows).compile().memory_analysis()
    p_bytes = sum(x.size * 4 for x in jax.tree.leaves(p32))
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
