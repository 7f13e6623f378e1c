"""The control, put in the program's place at a small size on the CPU: the
reference in bfloat16 fails the numbers a training cell compares, under the
cell's own limits, where the float32 reference against itself passes."""
import jax.numpy as jnp
import pytest

from chipbench import check, spec, traffic
from chipbench.reference import dense
from chipbench.reference.common import seed_key
from chipbench.reference.follow import follow
from chipbench.tests.small import DENSE

CASES = {"smollm-360m.train": (dense, DENSE),
         "smollm-360m.train_ckpt": (dense, DENSE)}
GAPS = ("loss_gap", "gnorm_gap", "grad_gap", "change_gap")


@pytest.mark.parametrize("cell", sorted(CASES))
def test_bfloat16_control_fails(cell):
    ref, conf = CASES[cell]
    limits = {k: spec.load_cell(cell).limits[k] for k in GAPS}
    m = ref.dims(conf)
    B, S = conf["train"]["batch"], conf["train"]["seq"]
    rows = [traffic.batch(5, i, B, S, m["vocab_size"], structured=True,
                          noise=0.1) for i in range(3)]
    batches = [(jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]))
               for b in rows]
    opt = conf["train"]["optimizer"]
    args = (ref, m, opt, seed_key(5), batches)
    full = follow(*args, compute_dtype=jnp.float32, param_dtype=jnp.float32,
                  row_block=2)
    again = follow(*args, compute_dtype=jnp.float32,
                   param_dtype=jnp.float32, row_block=4)
    low = follow(*args, compute_dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, row_block=2, precision="default")
    assert check.verdict(check.training_gaps(again, full), limits)[0]
    gaps = check.training_gaps(low, full)
    assert not check.verdict(gaps, limits)[0], gaps
    # bfloat16 parameters do not hold most of a step this small
    assert gaps["change_gap"] > 0.5
