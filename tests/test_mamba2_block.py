"""The Mamba-2 block at a small width on the CPU, with the published
initialisation (A = -exp(A_log), A_log = log U(1, 16); dt in logU(1e-3,
1e-1)) and the published chunk of 256 steps over 512 positions: there the
within-chunk decay exp(seg) above the diagonal overflows float32, so a scan
that masks after the exponential gives a NaN gradient. The program's loss
and gradients are held to the plain float32 reference
(``chipbench/reference/mamba2.py``), and the block's gated norm to the
published RMSNorm(y * silu(z)) * w."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model_under_test as mut
from chipbench.reference import mamba2 as ref
from chipbench.reference.common import seed_key
from repro.models import Model
from repro.models.layers import rmsnorm
from repro.models.mamba2 import _causal_conv, mamba2_forward, ssd_chunked

pytestmark = pytest.mark.slow

CHUNK, SEQ = 256, 512
CONF = {
    "name": "mamba2-small", "family": "mamba2",
    "d_model": 64, "n_layer": 2, "vocab_size": 250,
    "ssm_cfg": {"d_state": 16, "expand": 2, "headdim": 16,
                "chunk_size": CHUNK},
    "norm_epsilon": 1e-5, "initializer_range": 0.02,
    "reference": {"head_group": 4},
    "program": {"arch": "mamba2-2.7b", "fields": {
        "family": "ssm", "n_layers": 2, "d_model": 64, "vocab_size": 250,
        "ssm_state": 16, "ssm_expand": 2, "ssm_headdim": 16,
        "ssm_chunk": CHUNK, "norm_eps": 1e-5, "tie_embeddings": True,
        "dtype": "float32", "remat": True, "use_ssd_kernel": False}},
}
# exp overflows float32 above this
EXP_MAX = math.log(np.finfo(np.float32).max)


@pytest.fixture(scope="module")
def setup():
    m = ref.dims(CONF)
    params = jax.jit(lambda k: ref.init_params(k, m, jnp.float32))(
        seed_key(3))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, m["vocab_size"], (2, SEQ + 1)),
                       jnp.int32)
    return m, params, toks[:, :-1], toks[:, 1:]


def test_published_initialisation_overflows_the_unmasked_decay(setup):
    """The weights here do reach the overflow the mask guards against: a
    head's log-decay over one chunk, at its initial dt, passes float32's
    largest exponent."""
    _, params, _, _ = setup
    lp = params["layers"]
    dt0 = jax.nn.softplus(lp["dt_bias"])
    assert float(jnp.max(dt0 * jnp.exp(lp["A_log"]))) * (CHUNK - 1) > EXP_MAX


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["chunked", "ssd_scan_ref"])
def test_loss_and_gradients_finite_and_match_reference(setup, use_kernel):
    """The chunked XLA scan, and the SSD kernel's path whose gradient is
    ``ssd_scan_ref``'s VJP, against the reference's quadratic form at
    float32 and the highest matmul precision. Tolerances: the two forms sum
    the same terms in different orders and the chunked one carries the
    state between chunks, so they differ by float32 rounding, relative
    1e-5 on the loss and 1e-4 of each leaf's largest gradient."""
    m, params, tokens, targets = setup
    cfg = mut.program_config(CONF).replace(use_ssd_kernel=use_kernel)
    batch = {"tokens": tokens, "targets": targets}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(Model(cfg).loss))(params, batch)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, m, tokens, targets)))(params)
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    assert math.isfinite(float(lp))
    assert [jax.tree_util.keystr(path) for path, a in flat
            if not bool(jnp.isfinite(a).all())] == []
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for (path, a), b in zip(flat, jax.tree.leaves(gr)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale, name


def test_block_output_is_the_published_gated_norm(setup):
    """mamba2_forward's output is out_proj(RMSNorm(y * silu(z)) * w), with
    y the SSD's output, written out plainly here; w is random so that its
    place matters."""
    m, params, _, _ = setup
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    lp["norm_w"] = jax.random.uniform(jax.random.PRNGKey(5),
                                      lp["norm_w"].shape, jnp.float32, 0.5, 2)
    u = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, m["d_model"]))
    N, Din = m["d_state"], lp["out_proj"].shape[0]
    H = lp["A_log"].shape[0]
    with jax.default_matmul_precision("highest"):
        out, _ = mamba2_forward(lp, u, chunk=CHUNK)
        z = u @ lp["in_z"]
        x = _causal_conv(u @ lp["in_x"], lp["conv_x"], lp["conv_x_b"])
        bc = _causal_conv(u @ lp["in_bc"], lp["conv_bc"], lp["conv_bc_b"])
        dt = jax.nn.softplus(u @ lp["in_dt"] + lp["dt_bias"])
        y, _ = ssd_chunked(x.reshape(2, SEQ, H, Din // H), dt, bc[..., :N],
                           bc[..., N:], lp["A_log"], lp["D"], CHUNK)
        g = y.reshape(2, SEQ, Din) * (z / (1 + jnp.exp(-z)))
        g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + 1e-5)
        want = (g * lp["norm_w"]) @ lp["out_proj"]
        # the departure RMSNorm(y) * silu(z), which the block must not be
        departed = (rmsnorm(y.reshape(2, SEQ, Din), lp["norm_w"])
                    * jax.nn.silu(z)) @ lp["out_proj"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(departed - want))) > 1e-2
