"""Int8 gradient compression: quantisation error bounds + shard_map DP step
numerics vs the exact path."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis_support import given, settings, st
from jax.sharding import PartitionSpec as P


from repro.optim.compression import (compressed_grads, dequantize_int8,
                                     quantize_int8)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(1e-3, 1e3))
def test_quant_roundtrip_error_bound(seed, scale):
    g = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale
    q, s = quantize_int8(g)
    back = dequantize_int8(q, s)
    # absmax quantisation: error <= scale/2 = absmax/254 per element
    bound = float(jnp.max(jnp.abs(g))) / 254.0 + 1e-9
    assert float(jnp.max(jnp.abs(back - g))) <= bound * 1.01


def test_quant_roundtrip_error_bound_deterministic():
    """Pure-pytest fallback for the roundtrip property."""
    for seed, scale in ((0, 1.0), (1, 1e-3), (2, 1e3)):
        g = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale
        q, s = quantize_int8(g)
        back = dequantize_int8(q, s)
        bound = float(jnp.max(jnp.abs(g))) / 254.0 + 1e-9
        assert float(jnp.max(jnp.abs(back - g))) <= bound * 1.01


def test_compressed_psum_matches_mean():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 32))

    def f(g):
        return compressed_grads({"w": g}, "data")["w"]

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data")))(g)
    # single-host mean == identity up to quantisation error
    rel = float(jnp.max(jnp.abs(out - g)) / jnp.max(jnp.abs(g)))
    assert rel < 1e-2


@pytest.mark.slow  # full model + optimizer step: jax e2e tier
def test_dp_step_with_compression_close_to_exact():
    """A tiny DP train step with compressed grads stays within quantisation
    tolerance of the exact step (same params, same batch)."""
    from repro.launch.train import PRESETS
    from repro.models import Model
    from repro.optim import AdamWConfig, adamw_init, adamw_update

    cfg = PRESETS["5m"]
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    acfg = AdamWConfig()
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                          cfg.vocab_size),
             "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                           cfg.vocab_size)}
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))

    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    p_exact, _, _ = adamw_update(grads, params, opt, acfg)

    def reduce_fn(g):
        return compressed_grads(g, "data")
    gq = jax.jit(jax.shard_map(reduce_fn, mesh=mesh,
                               in_specs=P(), out_specs=P()))(grads)
    p_comp, _, _ = adamw_update(gq, params, opt, acfg)
    deltas = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                           b.astype(jnp.float32)))),
        p_exact, p_comp)
    assert max(jax.tree.leaves(deltas)) < 5e-3
