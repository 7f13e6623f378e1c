"""Per-architecture smoke tests (deliverable f): reduced config, one
forward + one real train step on CPU, asserting shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_smoke_config
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.models.layers import pad_vocab, rmsnorm

B, S = 2, 32


pytestmark = pytest.mark.slow  # jax model / e2e tier (CI runs -m "not slow")


def make_batch(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.input_mode == "tokens":
        return {"tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
                "targets": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    if cfg.input_mode == "embeds":
        return {"embeds": jax.random.normal(k1, (B, S, cfg.d_model)),
                "targets": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    return {"vision_embeds": jax.random.normal(k1, (B, cfg.vision_seq,
                                                    cfg.d_model)),
            "tokens": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(k3, (B, S), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.tree.map(lambda *_: 0, params, axes)), "axes tree must match"
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"
    gnorms = [jnp.linalg.norm(g.astype(jnp.float32)) for g in
              jax.tree.leaves(grads)]
    assert all(jnp.isfinite(g) for g in gnorms), f"{arch}: non-finite grads"
    opt = adamw_init(params)
    new_p, new_opt, gn = adamw_update(grads, params, opt, AdamWConfig())
    assert jnp.isfinite(gn)
    assert all(jnp.isfinite(l.astype(jnp.float32)).all()
               for l in jax.tree.leaves(new_p))


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get_smoke_config(a).supports_decode
                                  and get_smoke_config(a).input_mode == "tokens"])
def test_prefill_decode_consistency(arch):
    """Greedy decode after prefill produces logits of the right shape and
    valid (finite) values; cache pos advances. For the pure state-space
    family, prefill then decode through the cache equals the full forward
    pass on logits."""
    cfg = get_smoke_config(arch)
    if cfg.family == "ssm":
        _check_ssm_prefill_decode_matches_forward(cfg)
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    logits, state = jax.jit(lambda p, b: model.prefill(p, b, 64))(
        params, {"tokens": toks})
    assert logits.shape == (B, pad_vocab(cfg.vocab_size))
    assert bool(jnp.isfinite(logits).all())
    nxt = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)
    for _ in range(3):
        logits, state = jax.jit(model.decode_step)(params, state, nxt)
        assert bool(jnp.isfinite(logits).all())
        nxt = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)


def _check_ssm_prefill_decode_matches_forward(cfg, n_decode=8):
    """Prefill S tokens, then decode the next ``n_decode`` of the same
    sequence one by one; each step's logits against the full forward pass
    over all S + n_decode tokens at that position. In float32 at the
    highest matmul precision, so the chunked scan and the recurrent update
    differ by rounding only: 1e-4, relative to the logits' largest."""
    from repro.models.model import _lm_logits, _ssm_backbone
    cfg = cfg.replace(dtype=jnp.float32)
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + n_decode), 0,
                              cfg.vocab_size)

    def full(p, t):
        h = _ssm_backbone(p, cfg, jnp.take(p["embed"], t, axis=0))
        return _lm_logits(p, cfg, rmsnorm(h, p["final_norm"], cfg.norm_eps))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(full)(params, toks)[:, S - 1:]
        logits, state = jax.jit(lambda p, b: model.prefill(p, b, S))(
            params, {"tokens": toks[:, :S]})
        got = [logits]
        for i in range(n_decode):
            logits, state = jax.jit(model.decode_step)(params, state,
                                                       toks[:, S + i])
            got.append(logits)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(jnp.stack(got, axis=1)),
                               np.asarray(want), rtol=0, atol=1e-4 * scale)


def test_full_configs_match_assignment():
    """The full configs carry the exact assigned hyperparameters."""
    from repro.configs import get_config
    spec = {
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
    }
    for arch, (L, D, H, KV, F, V) in spec.items():
        c = get_config(arch)
        got = (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
               c.moe_d_ff if c.name == "qwen2-moe-a2.7b" else c.d_ff,
               c.vocab_size)
        assert got == (L, D, H, KV, F, V), f"{arch}: {got}"
    assert get_config("mixtral-8x22b").n_experts == 8
    assert get_config("qwen2-moe-a2.7b").n_experts == 60
    assert get_config("mamba2-2.7b").ssm_state == 128
    assert get_config("zamba2-1.2b").ssm_state == 64
