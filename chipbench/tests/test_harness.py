"""A whole run of a cell, less the look for a chip, at a small size on the
CPU: a sound run is correct, and each fault a training cell can have,
planted in the timed path, makes it not correct."""
import functools
import time

import jax
import pytest

from chipbench import spec, window
from chipbench.tests.small import DENSE

CELL = "smollm-360m.train_ckpt"
PEAKS = {"bf16_flops": 197e12}


def _cell():
    cell = spec.load_cell(CELL)
    cell.config = DENSE
    cell.mix = cell.mix | {"checkpoint": cell.mix["checkpoint"] | {"at_s": 0.2}}
    return cell


def _run(make_step=None):
    return window.run(_cell(), seed=2**31 + 7, seconds=0.6, trace=False,
                      t_start=time.monotonic(), peaks=PEAKS,
                      make_step=make_step)


def _unchanged(model, opt):
    """A step that computes the loss and returns its state unchanged."""
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        return params, opt_state, model.loss(params, batch), 0.0
    return step


def _half_batch(model, opt):
    """The program's step on the first half of the batch's rows."""
    from repro.launch.train import make_train_step
    inner = make_train_step(model, opt)

    def step(params, opt_state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return inner(params, opt_state, half)
    return step


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s",
                                   "ckpt_commit_s"}
    assert out["window"]["compiles"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_fault_is_not_correct(fault):
    out = _run(fault)
    assert not out["correct"], out["checks"]


def test_altered_token_is_not_correct(monkeypatch):
    """The program's corpus hands over a batch with one token changed."""
    from repro.data import SyntheticCorpus
    produce = SyntheticCorpus.batch

    def altered(self, step):
        out = produce(self, step)
        out["tokens"][0, 0] = (out["tokens"][0, 0] + 1) % self.vocab
        return out
    monkeypatch.setattr(SyntheticCorpus, "batch", altered)
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["checks"]["batch_mismatch"][0] > 0


def test_one_chip_builds_no_mesh():
    """A configuration without a layout traces the program's step with no
    mesh entered, on arguments that each live on one device."""
    from repro.distributed import current_mesh
    from repro.launch.train import make_train_step
    seen = []

    def recording(model, opt):
        inner = make_train_step(model, opt)

        def step(params, opt_state, batch):
            seen.append((current_mesh(), {
                len(a.sharding.device_set)
                for a in jax.tree.leaves((params, opt_state, batch))}))
            return inner(params, opt_state, batch)
        return step
    out = _run(recording)
    assert out["correct"], out["checks"]
    assert seen and all(mesh is None and n == {1} for mesh, n in seen)
    assert out["device"]["count"] == 1
