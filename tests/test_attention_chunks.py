"""The causal key-truncated attention path (``_causal_chunked_attn``)
against the dense one it replaces for causal sequences of two or more
chunks: the same outputs and gradients, each causal (query, key) pair
computed once, and the dispatcher's choice of path by shape and mask."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model, attention
from repro.models.attention import (_causal_chunked_attn, _dense_attn,
                                    causal_key_range, multihead_attn)


def _qkv(S, G, KV=2, hd=8, B=2, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, S, KV * G, hd), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(kv, (B, S, KV, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    return q, k, v, pos


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("window", [0, 37])
@pytest.mark.parametrize("G", [1, 3])
def test_chunked_equals_dense(G, window, chunk):
    S = 4 * chunk
    q, k, v, pos = _qkv(S, G)
    ct = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.float32)

    def run(fn):
        def out_and_grads(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(ct)
        return jax.jit(out_and_grads)(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = run(lambda q, k, v: _causal_chunked_attn(q, k, v, pos, window,
                                                       chunk))
        want = run(lambda q, k, v: _dense_attn(q, k, v, pos, pos, True,
                                               window))
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=0, err_msg=name)


@pytest.mark.parametrize("n", [2, 3, 8])
def test_each_causal_pair_in_exactly_one_chunk(n):
    chunk = 4
    S = n * chunk
    counts = np.zeros((S, S), int)
    for i in range(n):
        lo, hi = causal_key_range(i, chunk, 0)
        counts[i * chunk:(i + 1) * chunk, lo:hi] += 1
    causal = np.tril(np.ones((S, S), bool))
    assert (counts[causal] == 1).all()
    computed = counts.sum() / (S * S)
    assert computed == pytest.approx((n + 1) / (2 * n))


@pytest.mark.parametrize("window", [1, 5, 8, 13])
def test_windowed_chunk_covers_every_visible_key(window):
    chunk, n = 4, 6
    for i in range(n):
        lo, hi = causal_key_range(i, chunk, window)
        assert lo % chunk == 0 and hi == (i + 1) * chunk
        first_query = i * chunk
        assert lo <= max(0, first_query - window + 1)


def _attn_params(D=16, H=4, KV=2, hd=8):
    p, _ = attention.attn_init(jax.random.PRNGKey(0), D, H, KV, hd,
                               jnp.float32)
    return p


@pytest.mark.parametrize("causal,S,path", [
    (True, 2 * attention.CAUSAL_CHUNK, "chunked"),
    (False, 2 * attention.CAUSAL_CHUNK, "dense"),
    (True, attention.CAUSAL_CHUNK, "dense"),
    (True, 2 * attention.CAUSAL_CHUNK + 8, "dense"),
])
def test_dispatch_by_shape_and_mask(monkeypatch, causal, S, path):
    taken = []
    real_dense = _dense_attn

    def dense(*a, **kw):
        taken.append("dense")
        return real_dense(*a, **kw)

    def chunked(q, k, v, positions, window, chunk):
        # the real chunked path calls _dense_attn per chunk: stand in for it
        # with the real dense one, so only the dispatcher's choice is counted
        taken.append("chunked")
        assert chunk == attention.CAUSAL_CHUNK
        return real_dense(q, k, v, positions, positions, True, window)

    monkeypatch.setattr(attention, "_dense_attn", dense)
    monkeypatch.setattr(attention, "_causal_chunked_attn", chunked)
    p = _attn_params()
    x = jax.ShapeDtypeStruct((1, S, 16), jnp.float32)
    pos = jax.ShapeDtypeStruct((1, S), jnp.int32)
    jax.eval_shape(lambda x, pos: multihead_attn(p, x, pos, causal=causal),
                   x, pos)
    assert taken == [path]


def test_model_loss_and_grads_match_dense(monkeypatch):
    chunk = 16
    cfg = dataclasses.replace(get_smoke_config("tinyllama-1.1b"),
                              dtype=jnp.float32)
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    S = 2 * chunk
    batch = {"tokens": jax.random.randint(k1, (2, S), 0, cfg.vocab_size),
             "targets": jax.random.randint(k2, (2, S), 0, cfg.vocab_size)}
    taken = []
    real_chunked = _causal_chunked_attn

    def chunked(*a):
        taken.append(a[-1])
        return real_chunked(*a)

    def loss_and_grads():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(model.loss))(params, batch)

    monkeypatch.setattr(attention, "_causal_chunked_attn", chunked)
    monkeypatch.setattr(attention, "CAUSAL_CHUNK", chunk)
    loss_c, grads_c = loss_and_grads()
    assert taken and set(taken) == {chunk}
    monkeypatch.setattr(attention, "CAUSAL_CHUNK", S + 1)   # forced dense
    n_taken = len(taken)
    loss_d, grads_d = loss_and_grads()
    assert len(taken) == n_taken
    np.testing.assert_allclose(float(loss_c), float(loss_d), rtol=1e-6)
    for gc, gd in zip(jax.tree.leaves(grads_c), jax.tree.leaves(grads_d)):
        np.testing.assert_allclose(np.asarray(gc), np.asarray(gd),
                                   atol=1e-5, rtol=1e-5)


FLASH_BLOCK = attention.flash_ops.flash_block(2048, 64)


@pytest.mark.parametrize("S,hd,causal,backend,mesh_devices,path", [
    (2048, 64, True, "tpu", 1, "flash"),
    (2048, 128, True, "tpu", 1, "flash"),
    (2048, 64, True, "cpu", 1, "causal_chunks"),
    (8192, 64, True, "cpu", 1, "chunked"),
    (8192, 64, True, "tpu", 1, "flash"),
    (2048, 64, False, "tpu", 1, "dense"),
    (2048, 64, False, "cpu", 1, "dense"),
    (256, 64, True, "tpu", 1, "flash"),       # two blocks of 128
    (128, 64, True, "tpu", 1, "dense"),       # one block
    (2 * FLASH_BLOCK + 256, 64, True, "tpu", 1, "causal_chunks"),
    (2048 + 8, 64, True, "tpu", 1, "dense"),
    (2048, 80, True, "tpu", 1, "causal_chunks"),
    (2048, 64, True, "tpu", 4, "causal_chunks"),
])
def test_attention_path(S, hd, causal, backend, mesh_devices, path):
    assert attention.attention_path(S, hd, causal, backend,
                                    mesh_devices=mesh_devices) == path


@pytest.mark.parametrize("window", [0, 96])
def test_forced_flash_matches_chunked(window):
    """``use_flash`` on the CPU (interpret mode) against the causal chunked
    path it replaces on the TPU: output and gradients of the layer."""
    S, D = 256, 64
    p, _ = attention.attn_init(jax.random.PRNGKey(0), D, 6, 2, 32,
                               jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))

    def run(use_flash, chunk):
        def f(p, x):
            return multihead_attn(p, x, pos, causal=True, window=window,
                                  use_flash=use_flash)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(attention, "CAUSAL_CHUNK", chunk)
            with jax.default_matmul_precision("highest"):
                out, vjp = jax.vjp(f, p, x)
                ct = jax.random.normal(jax.random.PRNGKey(2), out.shape)
                return out, vjp(ct)

    out_f, grads_f = run(True, 64)
    out_c, grads_c = run(False, 64)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_c),
                               atol=1e-5, rtol=1e-5)
    for gf, gc in zip(jax.tree.leaves(grads_f), jax.tree.leaves(grads_c)):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gc),
                                   atol=1e-4, rtol=1e-4)
