"""Each plain reference agrees with the program's model at small sizes on
the CPU, and a reference computed in bfloat16 does not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model_under_test as mut
from chipbench.check import worst_leaf_gap
from chipbench.reference import dense, ssm
from chipbench.reference.common import seed_key
from chipbench.reference.follow import leaf_norms, to_floats
from chipbench.tests.small import DENSE, SSM

FAMILIES = {"dense": (dense, DENSE), "ssm": (ssm, SSM)}


def _setup(family):
    ref, conf = FAMILIES[family]
    m = ref.dims(conf)
    cfg = mut.program_config(conf)
    params = jax.jit(lambda k: ref.init_params(k, m, jnp.float32))(
        seed_key(7))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, m["vocab_size"], (2, conf["train"]["seq"] + 1))
    toks = jnp.asarray(toks, jnp.int32)
    return ref, m, cfg, params, toks[:, :-1], toks[:, 1:]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_layout_matches_the_program(family):
    ref, m, cfg, params, *_ = _setup(family)
    mut.check_layout(cfg, params)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_loss_logits_and_gradient_agree(family):
    from repro.models import Model
    ref, m, cfg, params, tokens, targets = _setup(family)
    model = Model(cfg)
    batch = {"tokens": tokens, "targets": targets}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss)(params, batch)
        lr_, gr = jax.value_and_grad(
            lambda p: ref.loss(p, m, tokens, targets))(params)
        last_p = model.prefill(params, {"tokens": tokens}, tokens.shape[1])[0]
        last_r = ref.logits(params, m, tokens)[:, -1]
    assert abs(float(lp) - float(lr_)) < 1e-5 * abs(float(lr_))
    np.testing.assert_allclose(np.asarray(last_p)[:, :m["vocab_size"]],
                               np.asarray(last_r), rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_bfloat16_reference_departs(family):
    """The first gradient of a bfloat16 reference is further from the
    float32 one than the program's is, by a wide margin."""
    from repro.models import Model
    ref, m, cfg, params, tokens, targets = _setup(family)
    batch = {"tokens": tokens, "targets": targets}
    with jax.default_matmul_precision("highest"):
        full = leaf_norms(jax.grad(
            lambda p: ref.loss(p, m, tokens, targets))(params))
        prog = leaf_norms(jax.grad(Model(cfg).loss)(params, batch))
        half = leaf_norms(jax.grad(lambda p: ref.loss(
            p, m, tokens, targets, jnp.bfloat16))(params))
    full, prog, half = map(to_floats, (full, prog, half))
    assert worst_leaf_gap(half, full) > 1e-4
    assert worst_leaf_gap(half, full) > 100 * worst_leaf_gap(prog, full)


def test_quadratic_ssd_equals_the_recurrence():
    rng = np.random.default_rng(1)
    b, S, H, P, N = 2, 24, 4, 3, 5
    x = jnp.asarray(rng.normal(size=(b, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 4, (H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, S, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, S, N)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = ssm.ssd(x, dt, A, B, C, head_group=2)
    h = np.zeros((b, H, N, P))
    want = np.zeros((b, S, H, P))
    xn, dtn, Bn, Cn, An = map(np.asarray, (x, dt, B, C, A))
    for t in range(S):
        h = h * np.exp(dtn[:, t, :, None, None] * An[None, :, None, None]) \
            + np.einsum("bn,bhp->bhnp", Bn[:, t], xn[:, t] * dtn[:, t, :, None])
        want[:, t] = np.einsum("bn,bhnp->bhp", Cn[:, t], h)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)
