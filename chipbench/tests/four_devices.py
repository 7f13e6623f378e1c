"""Runs of a cell under a four-chip layout on four virtual CPU devices, for
``test_four_devices.py``. The device count is fixed when JAX starts, so this
runs in a process of its own:

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python -m chipbench.tests.four_devices <dense|ssm>

It prints one JSON line: a sound run, the placement of every leaf of the
train state as the timed step receives it, a run with half of each batch
left out, and the reference followed on the four devices against the same
reference on one.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from chipbench import check, spec, traffic, window
from chipbench.reference.common import seed_key
from chipbench.reference.follow import follow
from chipbench.tests.small import DENSE, SSM
from chipbench.tests.test_harness import PEAKS, _cell, _half_batch

LAYOUT = {"mesh": {"data": 4, "model": 1}, "strategy": "fsdp"}
SEED = 2**31 + 11


def _placement(tree) -> list:
    """[[leaf, devices holding it, its bytes, their share on the fullest]]"""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        shard = max(s.data.nbytes for s in leaf.addressable_shards)
        out.append([jax.tree_util.keystr(path), len(leaf.sharding.device_set),
                    leaf.nbytes, shard / leaf.nbytes])
    return out


def main(family: str) -> dict:
    assert len(jax.devices()) == 4, jax.devices()
    conf = {"dense": DENSE, "ssm": SSM}[family] | {"layout": LAYOUT}
    cell = _cell()
    cell.config, cell.chips = conf, 4
    spec.check_layout(cell.name, cell.chips, conf)
    seen = {}

    def recording(model, opt):
        from repro.launch.train import make_train_step
        inner = make_train_step(model, opt)

        def step(params, opt_state, batch):
            if not seen:
                seen["state"] = _placement((params, opt_state))
                seen["batch"] = _placement(batch)
            return inner(params, opt_state, batch)
        return step

    def run(make_step):
        out = window.run(cell, seed=SEED, seconds=0.6, trace=False,
                         t_start=time.monotonic(), peaks=PEAKS,
                         make_step=make_step)
        return {k: out[k] for k in ("correct", "failed", "device", "checks")}

    sound = run(recording)
    half = run(_half_batch)

    ref = cell.reference
    m = ref.dims(conf)
    B, S = conf["train"]["batch"], conf["train"]["seq"]
    batches = [traffic.batch(SEED, i, B, S, m["vocab_size"], structured=True,
                             noise=0.1) for i in range(3)]
    batches = [(jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]))
               for b in batches]
    args = (ref, m, conf["train"]["optimizer"], seed_key(SEED), batches)
    kw = dict(compute_dtype=jnp.float32, param_dtype=jnp.float32,
              row_block=conf["reference"]["row_block"])
    spread = follow(*args, devices=jax.devices(), **kw)
    whole = follow(*args, **kw)
    return {"sound": sound, "state": seen["state"], "batch": seen["batch"],
            "half_batch": half,
            "reference_gaps": check.training_gaps(spread, whole)}


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1])), flush=True)
