"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracle
(interpret mode on CPU) + gradients through the custom_vjp wrappers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.ssd_scan import ssd_scan, ssd_scan_ref


pytestmark = pytest.mark.slow  # jax model / e2e tier (CI runs -m "not slow")


def rnd(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(dtype)


FLASH_CASES = [
    # (B, S, H, KV, hd, causal, window, bq, bk)
    (1, 128, 4, 4, 64, True, 0, 64, 64),
    (2, 256, 8, 2, 64, True, 0, 128, 64),
    (1, 256, 4, 4, 32, False, 0, 128, 128),
    (2, 128, 4, 2, 64, True, 32, 64, 64),
    (1, 512, 2, 1, 128, True, 128, 128, 128),
    (1, 128, 4, 4, 64, True, 0, 128, 32),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_flash_matches_ref(case, dtype, tol):
    B, S, H, KV, hd, causal, win, bq, bk = case
    q = rnd(1, (B, S, H, hd), dtype)
    k = rnd(2, (B, S, KV, hd), dtype)
    v = rnd(3, (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal, win, bq, bk)
    ref = attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


GRAD_CASES = FLASH_CASES + [
    (1, 128, 4, 2, 32, True, 0, 64, 64),
    # GQA with G = 3 at hd 64, SmolLM-360M's head layout
    (1, 256, 6, 2, 64, True, 0, 64, 64),
]


@pytest.mark.parametrize("case", GRAD_CASES)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 5e-2)])
def test_flash_grads_match_ref(case, dtype, tol):
    """dQ, dK and dV through the backward kernels against autodiff of the
    oracle, for a random output cotangent."""
    B, S, H, KV, hd, causal, win, bq, bk = case
    q = rnd(4, (B, S, H, hd), dtype)
    k = rnd(5, (B, S, KV, hd), dtype)
    v = rnd(6, (B, S, KV, hd), dtype)
    ct = rnd(7, (B, S, H, hd), dtype)
    _, vjp = jax.vjp(lambda *a: flash_attention(*a, causal, win, bq, bk),
                     q, k, v)
    _, vjp_ref = jax.vjp(
        lambda *a: attention_ref(*a, causal=causal, window=win), q, k, v)
    for name, g1, g2 in zip(("dq", "dk", "dv"), vjp(ct), vjp_ref(ct)):
        assert g1.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(g1, np.float32),
                                   np.asarray(g2, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


SSD_CASES = [
    # (b, nc, Q, H, P, N)
    (1, 4, 32, 8, 32, 16),
    (2, 2, 64, 4, 16, 32),
    (1, 8, 16, 16, 64, 128),
    (1, 2, 128, 8, 64, 64),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 5e-2)])
def test_ssd_matches_ref(case, dtype, tol):
    b, nc, Q, H, P, N = case
    x = rnd(7, (b, nc, Q, H, P), dtype) * 0.5
    dt = jax.nn.softplus(rnd(8, (b, nc, Q, H), jnp.float32))
    Bm, Cm = rnd(9, (b, nc, Q, N), jnp.float32), rnd(10, (b, nc, Q, N), jnp.float32)
    la = dt * (-jnp.exp(rnd(11, (H,), jnp.float32) * 0.2))
    D = jnp.ones((H,))
    y1, h1 = ssd_scan(x, dt, Bm, Cm, la, D)
    y2, h2 = ssd_scan_ref(x, dt, Bm, Cm, la, D)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=tol, rtol=tol)


def test_ssd_state_continuity():
    """Final state from one call seeds sequential decode equivalence: the
    chunked scan must equal a plain step-by-step recurrence."""
    b, nc, Q, H, P, N = 1, 2, 16, 4, 8, 8
    x = rnd(12, (b, nc, Q, H, P), jnp.float32) * 0.3
    dt = jax.nn.softplus(rnd(13, (b, nc, Q, H), jnp.float32))
    Bm, Cm = rnd(14, (b, nc, Q, N), jnp.float32), rnd(15, (b, nc, Q, N), jnp.float32)
    A = -jnp.exp(rnd(16, (H,), jnp.float32) * 0.1)
    la = dt * A
    D = jnp.zeros((H,))
    _, h_last = ssd_scan_ref(x, dt, Bm, Cm, la, D)
    # naive per-step recurrence
    h = jnp.zeros((b, H, N, P))
    S = nc * Q
    xf = x.reshape(b, S, H, P)
    dtf = dt.reshape(b, S, H)
    Bf, Cf = Bm.reshape(b, S, N), Cm.reshape(b, S, N)
    for t in range(S):
        dec = jnp.exp(dtf[:, t] * A)
        h = h * dec[..., None, None] + jnp.einsum(
            "bn,bhp->bhnp", Bf[:, t], xf[:, t] * dtf[:, t][..., None])
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_last),
                               atol=1e-4, rtol=1e-4)


def test_model_level_kernel_equivalence():
    from repro.configs import get_smoke_config
    from repro.models import Model
    for arch, flag in [("tinyllama-1.1b", "use_flash"),
                       ("mamba2-2.7b", "use_ssd_kernel"),
                       ("zamba2-1.2b", "use_ssd_kernel")]:
        cfg0 = get_smoke_config(arch).replace(dtype=jnp.float32)
        cfg1 = cfg0.replace(**{flag: True})
        m0, m1 = Model(cfg0), Model(cfg1)
        p, _ = m0.init(jax.random.PRNGKey(1))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 64),
                                              0, cfg0.vocab_size),
                 "targets": jax.random.randint(jax.random.PRNGKey(3), (2, 64),
                                               0, cfg0.vocab_size)}
        l0, l1 = m0.loss(p, batch), m1.loss(p, batch)
        assert abs(float(l0) - float(l1)) < 1e-3, arch
