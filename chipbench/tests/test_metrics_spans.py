"""The per-layer metrics read from the program's spans, fed the records of
a small save and loader run taken under the profiler on the CPU."""
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from chipbench import spec
from repro.checkpoint import CheckpointManager
from repro.core import Cluster, IORuntime, RealBackend
from repro.data import PrefetchLoader, SyntheticCorpus
from repro.obs import spans

CKPT = ("ckpt_snapshot_s", "ckpt_submit_s", "ckpt_blocked_s",
        "rt_lock_wait_s", "io_queue_s")
ALL = CKPT + ("loader_wait_ms",)


def _tree():
    return {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
            "m": jnp.ones((64, 64), jnp.float32),
            "v": jnp.zeros((33,), jnp.bfloat16)}


def _read(name):
    return spec.metric_reader(name)({})


def _run(tmp, save: bool):
    """Loader steps, and with ``save`` one async save through the runtime
    while another thread holds the runtime's lock for a moment."""
    loader = PrefetchLoader(SyntheticCorpus(500, 8, 2, seed=5), depth=2)
    mgr = CheckpointManager(tmp / "ckpt", n_shards=4)
    cluster = Cluster.make(n_workers=1, cpus=2, io_executors=2)
    with IORuntime(cluster, backend=RealBackend()) as rt:
        for step in range(4):
            loader.get(step)
        if save:
            tree, held = jax.block_until_ready(_tree()), threading.Event()

            def hold():
                with rt.lock:
                    held.set()
                    threading.Event().wait(0.3)
            holder = threading.Thread(target=hold)
            holder.start()
            assert held.wait(5)
            assert mgr.save(3, tree)
            holder.join(5)
            assert not holder.is_alive()
            mgr.wait()


def _traced(tmp, save: bool):
    spans.clear()
    with jax.profiler.trace(str(tmp / "trace")):
        _run(tmp, save)
    return spans.records()


@pytest.fixture
def save_records(tmp_path):
    yield _traced(tmp_path, save=True)
    spans.clear()


def _named(recs, name):
    return [r for r in recs if r.name.split(":", 1)[0] == name]


def test_readers_against_hand_sums(save_records):
    recs = save_records
    (save,) = _named(recs, "ckpt.save")
    (snap,) = _named(recs, "ckpt.snapshot")
    (submit,) = _named(recs, "ckpt.submit")
    wall = lambda r: (r.end_ns - r.start_ns) / 1e9
    off = lambda r: (r.end_ns - r.start_ns - r.cpu_ns) / 1e9
    assert _read("ckpt_snapshot_s") == wall(snap)
    assert _read("ckpt_submit_s") == wall(submit)
    assert _read("ckpt_blocked_s") == pytest.approx(off(save) - off(snap))

    mine = [r for r in recs if r.thread == save.thread
            and save.start_ns <= r.start_ns and r.end_ns <= save.end_ns]
    lock_s = sum(r.counts.get("lock_wait_ns", 0) for r in mine) / 1e9
    assert lock_s >= 0.01                  # the holder made the save wait
    assert _read("rt_lock_wait_s") == pytest.approx(lock_s)

    shard_runs = {r.parent for r in _named(recs, "ckpt.shard")}
    tids = {r.counts["tid"] for r in recs if r.id in shard_runs}
    assert len(tids) == 3                  # three leaves, four shards
    queued = [wall(r) for r in _named(recs, "io.queued")
              if r.counts["tid"] in tids]
    assert _read("io_queue_s") == max(queued)

    gets, waits = _named(recs, "loader.get"), _named(recs, "loader.wait")
    assert len(gets) == len(waits) == 4
    assert _read("loader_wait_ms") == pytest.approx(
        1000 * sum(wall(r) for r in waits) / 4)


def test_ckpt_readers_none_without_a_save(tmp_path):
    _traced(tmp_path, save=False)
    try:
        assert all(_read(name) is None for name in CKPT)
        assert _read("loader_wait_ms") > 0
    finally:
        spans.clear()


def test_none_without_records():
    spans.clear()
    assert all(_read(name) is None for name in ALL)


def test_none_while_a_save_is_open(save_records):
    (save,) = _named(save_records, "ckpt.save")
    save.end_ns = None
    assert all(_read(name) is None for name in CKPT)


def test_none_for_a_program_without_spans(save_records, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr("repro.obs.spans")
    assert all(_read(name) is None for name in ALL)
