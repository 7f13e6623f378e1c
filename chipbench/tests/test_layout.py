"""What a cell's ``chips`` asks of its configuration: a cell on several
chips needs a layout whose mesh holds exactly that many, and ``run.py``
refuses one that has none, or another size, before anything runs. And
``train_mfu`` counts the peak of every chip the cell runs on."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import spec
from chipbench.tests.small import DENSE

ROOT = Path(__file__).resolve().parents[2]
CELL = "smollm-360m.train"
LAYOUT = {"mesh": {"data": 4, "model": 1}, "strategy": "fsdp"}


def _bench(tmp_path, chips, config):
    """A BENCHMARK.json whose one cell takes ``chips`` and ``config``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf_file = tmp_path / "config.json"
    conf_file.write_text(json.dumps(config))
    bench["configs"][0]["file"] = str(conf_file)
    bench["workloads"] = [w | {"chips": chips} for w in bench["workloads"]
                          if w["name"] == CELL]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


@pytest.mark.parametrize("chips, layout, refusal", [
    (4, None, "states no layout"),
    (4, LAYOUT | {"mesh": {"data": 2, "model": 1}}, "is a mesh of 2"),
    (1, LAYOUT, "is a mesh of 4"),
])
def test_cell_that_cannot_be_laid_over_its_chips_is_refused(
        tmp_path, chips, layout, refusal):
    config = DENSE if layout is None else DENSE | {"layout": layout}
    with pytest.raises(SystemExit, match=refusal):
        spec.load_cell(CELL, _bench(tmp_path, chips, config))


@pytest.mark.parametrize("chips, layout", [(1, None), (4, LAYOUT)])
def test_cell_laid_over_its_chips_loads(tmp_path, chips, layout):
    config = DENSE if layout is None else DENSE | {"layout": layout}
    cell = spec.load_cell(CELL, _bench(tmp_path, chips, config))
    assert cell.chips == chips
    assert cell.config.get("layout") == layout


def test_run_refuses_a_cell_without_layout_before_anything_runs(tmp_path):
    """In a checkout whose cell asks for 4 chips of a configuration without
    a layout, ``run.py`` exits non-zero with the reason on standard error
    and prints no result."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [w | {"chips": 4} for w in bench["workloads"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "4 chips, but configuration 'smollm-360m' states no layout" \
        in done.stderr


def test_train_mfu_counts_every_chip():
    read = spec.metric_reader("train_mfu")
    ctx = {"flops_per_step": 3e15, "steps": 10, "window_s": 50.0,
           "peak": {"bf16_flops": 197e12}}
    one = read(ctx | {"chips": 1})
    assert one == pytest.approx(100.0 * 3e16 / 50.0 / 197e12)
    assert read(ctx | {"chips": 4}) == pytest.approx(one / 4)
