"""Analytic roofline model validation (EXPERIMENTS.md §Roofline methodology):
XLA's compiled cost_analysis counts while-loop bodies once, so the roofline
uses an analytic FLOPs model — validated here against XLA on a small
UNROLLED config where XLA's count is complete."""
import jax
import jax.numpy as jnp

from benchmarks.analytic import cell_cost, roofline_terms
from repro.configs.base import ModelConfig, ShapeCell
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init, adamw_update

CFG = ModelConfig(name="probe", family="dense", n_layers=2, d_model=128,
                  n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=512,
                  remat=True, unroll_layers=True)


def _train_flops():
    model = Model(CFG)
    params = jax.eval_shape(lambda r: model.init(r)[0], jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32),
             "targets": jax.ShapeDtypeStruct((4, 128), jnp.int32)}
    acfg = AdamWConfig()

    def step(p, o, b):
        loss, g = jax.value_and_grad(model.loss)(p, b)
        return adamw_update(g, p, o, acfg) + (loss,)
    c = jax.jit(step).lower(params, opt, batch).compile()
    return c.cost_analysis()["flops"]


def test_analytic_train_flops_within_25pct_of_xla():
    xla = _train_flops()
    an = cell_cost(CFG, ShapeCell("t", 128, 4, "train")).flops
    assert 0.75 < an / xla < 1.25, (an, xla)


def test_analytic_prefill_flops_within_30pct_of_xla():
    model = Model(CFG)
    params = jax.eval_shape(lambda r: model.init(r)[0], jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32)}
    c = jax.jit(lambda p, b: model.prefill(p, b, 128)).lower(
        params, batch).compile()
    xla = c.cost_analysis()["flops"]
    an = cell_cost(CFG, ShapeCell("p", 128, 4, "prefill")).flops
    assert 0.7 < an / xla < 1.3, (an, xla)


def test_roofline_terms_structure():
    t = roofline_terms(CFG, ShapeCell("t", 128, 4, "train"), n_devices=256,
                       collective_bytes_per_dev=1e9)
    assert t["dominant"] in ("compute", "memory", "collective")
    assert 0 < t["useful_ratio"] <= 1.0
    assert t["roofline_fraction"] <= 1.0


def test_model_flops_definition():
    # MODEL_FLOPS = 6*N*D for dense train, 6*N_active*D for MoE
    c = cell_cost(CFG, ShapeCell("t", 128, 4, "train"))
    assert c.model_flops == 6.0 * CFG.param_count() * 4 * 128
    moe = CFG.replace(n_experts=4, n_experts_per_tok=2, moe_d_ff=256, d_ff=0)
    cm = cell_cost(moe, ShapeCell("t", 128, 4, "train"))
    assert cm.model_flops == 6.0 * moe.active_param_count() * 4 * 128
    assert moe.active_param_count() < moe.param_count()
