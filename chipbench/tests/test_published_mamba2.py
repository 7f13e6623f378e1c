"""The ``mamba2`` family's reference and cell at the published chunk and
initialisation (``published_mamba2.py``), on the CPU: the reference agrees
with the program and a bfloat16 one does not; a run of the cell, on one
device and under its four-chip layout on four virtual devices, is correct
and finite, and the half-batch fault is not correct. With the scan's decay
masked after the exponential, the program's gradients here are NaN and no
run is correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model_under_test as mut
from chipbench.check import worst_leaf_gap
from chipbench.reference import mamba2 as ref
from chipbench.reference.common import seed_key
from chipbench.reference.follow import leaf_norms, to_floats
from chipbench.tests.published_mamba2 import MAMBA2, cell, run
from chipbench.tests.test_harness import _half_batch

ROOT = Path(__file__).resolve().parents[2]


def _setup():
    m = ref.dims(MAMBA2)
    params = jax.jit(lambda k: ref.init_params(k, m, jnp.float32))(
        seed_key(7))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, m["vocab_size"], (2, MAMBA2["train"]["seq"] + 1))
    toks = jnp.asarray(toks, jnp.int32)
    return m, mut.program_config(MAMBA2), params, toks[:, :-1], toks[:, 1:]


def test_reference_agrees_with_the_program_and_bfloat16_departs():
    from repro.models import Model
    m, cfg, params, tokens, targets = _setup()
    mut.check_layout(cfg, params)
    batch = {"tokens": tokens, "targets": targets}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(Model(cfg).loss)(params, batch)
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(p, m, tokens, targets))(params)
        last_p = Model(cfg).prefill(params, {"tokens": tokens},
                                    tokens.shape[1])[0]
        last_r = ref.logits(params, m, tokens)[:, -1]
        half = jax.grad(lambda p: ref.loss(p, m, tokens, targets,
                                           jnp.bfloat16))(params)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    np.testing.assert_allclose(np.asarray(last_p)[:, :m["vocab_size"]],
                               np.asarray(last_r), rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale
    full, prog, half = map(to_floats, map(leaf_norms, (gr, gp, half)))
    assert worst_leaf_gap(half, full) > 100 * worst_leaf_gap(prog, full)


def test_one_device_run_is_correct_and_half_batch_is_not():
    sound = run(cell(1))
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0
    assert all(map(np.isfinite, sound["window"]["first_losses"]))
    half = run(cell(1), _half_batch)
    assert not half["correct"], half["checks"]


def test_four_device_run_is_correct_and_half_batch_is_not():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                          "--xla_force_host_platform_device_count=4"))
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.published_mamba2"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    out = json.loads(done.stdout.splitlines()[-1])
    sound, half = out["sound"], out["half_batch"]
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0
    assert sound["device"]["count"] == 4
    assert not half["correct"], half["checks"]
