"""A small Mamba-2 configuration of the ``mamba2`` family at the published
chunk of 256 steps over 512 positions, where the published initialisation
of A and dt (``reference/ssm.py`` ``init_params``) makes the within-chunk
decay overflow float32 above the diagonal. Widths are cut, as in
``small.py``: this only feeds tests on the CPU. Run as a module, it runs
the ``mamba2-2.7b-full.train`` cell on this configuration under the
cell's four-chip layout on four virtual CPU devices and prints one JSON
line (``test_published_mamba2.py`` starts it):

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python -m chipbench.tests.published_mamba2
"""
from __future__ import annotations

import json
import time

from chipbench.tests.small import DENSE

CELL = "mamba2-2.7b-full.train"
MAMBA2 = {
    "name": "mamba2-small", "family": "mamba2",
    "d_model": 64, "n_layer": 2, "vocab_size": 250,
    "ssm_cfg": {"d_state": 16, "expand": 2, "headdim": 16, "chunk_size": 256},
    "norm_epsilon": 1e-5, "initializer_range": 0.02,
    "program": {"arch": "mamba2-2.7b", "fields": {
        "family": "ssm", "n_layers": 2, "d_model": 64, "vocab_size": 250,
        "ssm_state": 16, "ssm_expand": 2, "ssm_headdim": 16,
        "ssm_chunk": 256, "norm_eps": 1e-5, "tie_embeddings": True,
        "dtype": "float32", "remat": True, "use_ssd_kernel": False}},
    "train": DENSE["train"] | {"seq": 512},
    "reference": {"row_block": 2, "head_group": 4},
}


def cell(chips: int, layout=None):
    """The cell with this configuration, on ``chips`` devices."""
    from chipbench import spec
    c = spec.load_cell(CELL)
    c.config = MAMBA2 if layout is None else MAMBA2 | {"layout": layout}
    c.chips = chips
    spec.check_layout(c.name, chips, c.config)
    return c


def run(c, make_step=None, seed=2**31 + 13) -> dict:
    from chipbench import window
    from chipbench.tests.test_harness import PEAKS
    out = window.run(c, seed=seed, seconds=0.6, trace=False,
                     t_start=time.monotonic(), peaks=PEAKS,
                     make_step=make_step)
    return {k: out[k] for k in ("correct", "failed", "device", "checks",
                                "window")}


if __name__ == "__main__":
    import jax

    from chipbench.tests.four_devices import LAYOUT
    from chipbench.tests.test_harness import _half_batch
    assert len(jax.devices()) == 4, jax.devices()
    four = cell(4, LAYOUT)
    print(json.dumps({"sound": run(four), "half_batch": run(four, _half_batch)}),
          flush=True)
