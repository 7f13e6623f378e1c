"""A cell whose configuration states a four-chip layout, run through the
harness on four virtual CPU devices: the program's state is sharded over
them, a sound run is correct, the half-batch fault is not, and the
reference spread over the four devices follows the same steps as on one.
Each family runs in a process of its own (``four_devices.py``), since the
number of devices is fixed when JAX starts."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FAMILIES = ("dense", "ssm")


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(family):
        if family not in cache:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       PYTHONPATH=os.pathsep.join(
                           [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]),
                       XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                                  "--xla_force_host_platform_device_count=4"))
            done = subprocess.run(
                [sys.executable, "-m", "chipbench.tests.four_devices", family],
                cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
            assert done.returncode == 0, done.stderr[-4000:]
            cache[family] = json.loads(done.stdout.splitlines()[-1])
        return cache[family]
    return get


@pytest.mark.parametrize("family", FAMILIES)
def test_sound_run_on_four_devices_is_correct(runs, family):
    out = runs(family)["sound"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["device"]["count"] == 4
    assert len(out["device"]["memory_peak_bytes_per_chip"]) == 4


@pytest.mark.parametrize("family", FAMILIES)
def test_state_is_sharded_over_four_devices(runs, family):
    """Every leaf of the train state is on all four devices, each either
    split four ways or whole, and the fullest device holds little more
    than a quarter of the state's bytes: the weights, moments and batch
    are sharded, not copied."""
    run = runs(family)
    state = run["state"]
    assert all(n == 4 for _, n, _, _ in state), state
    assert {share for *_, share in state} == {0.25, 1.0}, state
    total = sum(nbytes for _, _, nbytes, _ in state)
    fullest = sum(nbytes * share for _, _, nbytes, share in state)
    assert fullest / total < 0.3, (fullest, total)
    assert all(n == 4 and share == 0.25 for _, n, _, share in run["batch"])


@pytest.mark.parametrize("family", FAMILIES)
def test_half_batch_on_four_devices_is_not_correct(runs, family):
    out = runs(family)["half_batch"]
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("family", FAMILIES)
def test_reference_spread_over_four_devices_agrees(runs, family):
    """The reference's own placement changes no number by more than
    float32 rounding in a different order of summation."""
    gaps = runs(family)["reference_gaps"]
    assert max(gaps.values()) < 1e-5, gaps
