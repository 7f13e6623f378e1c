"""Compile the main path's programs for a described TPU v5e chip.

Nothing runs: a described chip holds no arrays, so every program gets
ShapeDtypeStructs placed on it. The TPU compiler still refuses what the chip
would refuse and interpret mode never sees: block shapes the lowering does
not tile, kernels over the scoped VMEM limit, programs over the device's
memory. The topology is described inside a module fixture, never at import,
so every test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import (SERVE_BATCH, SERVE_NEW, SERVE_PROMPT, TRAIN_BATCH,
                        TRAIN_SEQ)
from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd
from repro.launch.train import make_train_step
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("window", [0, 512])
def test_flash_attention_compiles_at_tinyllama_widths(one_chip, window):
    B, S, H, KV, hd = 1, 2048, 32, 4, 64
    q = _sds(one_chip, (B, S, H, hd))
    kv = _sds(one_chip, (B, S, KV, hd))
    compiled = jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, causal=True, window=window)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    b, nc, Q, H, P, N = 1, 8, 256, 80, 64, 128
    f32 = jnp.float32
    args = (_sds(one_chip, (b, nc, Q, H, P)),
            _sds(one_chip, (b, nc, Q, H), f32),
            _sds(one_chip, (b, nc, Q, N), f32),
            _sds(one_chip, (b, nc, Q, N), f32),
            _sds(one_chip, (b, nc, Q, H), f32),
            _sds(one_chip, (H,), f32))
    compiled = jax.jit(ssd_scan_fwd).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tinyllama_train_step_fits_one_chip(one_chip):
    """The full-width step at the smoke's batch: arguments plus temporaries
    fit 16 GB, and the donated train state is aliased into the outputs."""
    model = Model(get_config("tinyllama-1.1b"))
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))[0]))
    opt_state = _on(one_chip, jax.eval_shape(adamw_init, params))
    batch = {k: _sds(one_chip, (TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
             for k in ("tokens", "targets")}
    mem = make_train_step(model, AdamWConfig()).lower(
        params, opt_state, batch).compile().memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves((params, opt_state)))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES, mem
    assert mem.alias_size_in_bytes >= state_bytes, mem


def test_tinyllama_serving_programs_compile(one_chip):
    """Prefill and one decode step at full width, at the smoke's serving
    shapes."""
    model = Model(get_config("tinyllama-1.1b"))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    tokens = {"tokens": jax.ShapeDtypeStruct((SERVE_BATCH, SERVE_PROMPT),
                                             jnp.int32)}
    prefill = lambda p, b: model.prefill(p, b, SERVE_PROMPT + SERVE_NEW)
    state = jax.eval_shape(prefill, params, tokens)[1]
    params, tokens, state = (_on(one_chip, t) for t in (params, tokens, state))
    jax.jit(prefill).lower(params, tokens).compile()
    nxt = _sds(one_chip, (SERVE_BATCH,), jnp.int32)
    mem = jax.jit(model.decode_step).lower(params, state, nxt).compile() \
        .memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES, mem
