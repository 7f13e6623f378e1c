"""Finds everything a cell needs by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix. The configuration is
``configs/<name>.json``; its ``family`` names the plain reference
(``reference/<family>.py``) and the operation count (``flops/<family>.py``).
The mix is ``mixes/<traffic>.json``. The limits the run's outputs are held to
are ``limits/<cell>.json``. Per-layer metrics are ``metrics/<metric>.py``.
Nothing here knows any one configuration, mix or metric.

A cell runs on the ``chips`` BENCHMARK.json gives it. A configuration that
runs on one chip leaves ``layout`` out: its run builds no mesh and JAX
places every array on the first chip. A configuration for several chips
states the program's own layout over them::

    "layout": {"mesh": {"data": 4, "model": 1}, "strategy": "fsdp"}

``mesh`` names the program's mesh axes and their sizes, whose product is
the cell's ``chips``; ``strategy`` is a key of the program's
``repro.distributed.sharding.STRATEGIES``. With it the run builds that mesh
over the first ``chips`` devices and, under the program's mesh context and
rules, makes the weights straight into the program's shardings, puts AdamW's
state beside them, splits each batch over the strategy's batch axes (the
configuration's ``train.batch`` is the global batch) and saves the sharded
state as the program does. The plain reference spreads its own state over
the same chips, by a placement of its own (``reference/follow.py``). Memory
is read on every chip, and ``train_mfu`` counts every chip's peak. A cell on
several chips whose configuration has no layout, or whose layout's mesh
holds another number of chips, is refused before anything runs.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<name>.json
    mix: dict             # mixes/<traffic>.json
    limits: dict          # limits/<cell>.json
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def reference(self):
        return importlib.import_module(
            f"chipbench.reference.{self.config['family']}")

    @property
    def flops(self):
        return importlib.import_module(
            f"chipbench.flops.{self.config['family']}")


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_path)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in {bench_path}")
    w = by_name[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / conf["file"])
    check_layout(name, w["chips"], config)
    return Cell(
        name=name, chips=w["chips"],
        config=config,
        mix=load_json(HERE / "mixes" / f"{w['traffic']}.json"),
        limits=load_json(HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def check_layout(name: str, chips: int, config: dict) -> None:
    """Refuse a cell whose configuration cannot be laid over its chips."""
    layout = config.get("layout")
    if layout is None:
        if chips > 1:
            raise SystemExit(f"{name}: {chips} chips, but configuration "
                             f"{config['name']!r} states no layout")
        return
    size = math.prod(layout["mesh"].values())
    if size != chips:
        raise SystemExit(f"{name}: {chips} chips, but the layout of "
                         f"{config['name']!r} is a mesh of {size}")


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``, loaded by path so
    that a metric's name may hold dots."""
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
