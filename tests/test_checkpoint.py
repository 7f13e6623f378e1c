"""Checkpoint substrate: roundtrip, atomic commit, async via runtime,
elastic restore."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import Cluster, IORuntime, RealBackend, StorageDevice, WorkerNode


def tree():
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones((4,), jnp.bfloat16),
            "opt": {"count": jnp.zeros((), jnp.int32),
                    "m": jnp.full((2, 2), 0.5)}}


def assert_tree_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x, np.float32), np.asarray(y, np.float32)), a, b)


def test_sync_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, n_shards=3)
    t = tree()
    mgr.save(5, t, sync=True)
    restored, step = mgr.restore(t)
    assert step == 5
    assert_tree_equal(t, restored)


def test_latest_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, n_shards=2, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree(), sync=True)
    assert mgr.latest_step() == 4
    assert mgr.steps() == [3, 4]  # gc keeps 2


def test_torn_manifest_ignored(tmp_path):
    mgr = CheckpointManager(tmp_path, n_shards=2)
    mgr.save(1, tree(), sync=True)
    mgr.save(2, tree(), sync=True)
    # simulate a torn step-3: shards written, manifest garbage
    d = tmp_path / "step_00000003"
    d.mkdir()
    (d / "MANIFEST.json").write_text("{not json")
    assert mgr.latest_step() == 2


def test_truncated_shard_detected(tmp_path):
    mgr = CheckpointManager(tmp_path, n_shards=1)
    t = tree()
    mgr.save(1, t, sync=True)
    shard = next((tmp_path / "step_00000001").glob("shard_*.bin"))
    shard.write_bytes(shard.read_bytes()[:-4])
    with pytest.raises(IOError, match="truncated"):
        mgr.restore(t)


def test_async_save_through_runtime(tmp_path):
    dev = StorageDevice(name="fs", bandwidth=2000, per_stream_cap=500)
    cluster = Cluster(workers=[WorkerNode(name="w0", cpus=2, io_executors=4,
                                          storage=dev)])
    mgr = CheckpointManager(tmp_path, n_shards=4)
    t = tree()
    with IORuntime(cluster, backend=RealBackend()):
        assert mgr.save(7, t)
        mgr.wait()
    restored, step = mgr.restore(t)
    assert step == 7
    assert_tree_equal(t, restored)


def test_restore_with_new_shardings(tmp_path):
    # elastic restart: restore onto explicit (here: single-device) shardings
    mgr = CheckpointManager(tmp_path, n_shards=2)
    t = tree()
    mgr.save(1, t, sync=True)
    sh = jax.tree.map(
        lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), t)
    restored, _ = mgr.restore(t, shardings=sh)
    assert_tree_equal(t, restored)
    leaf = jax.tree.leaves(restored)[0]
    assert isinstance(leaf, jax.Array)


@pytest.mark.parametrize("mode", ["sync", "flat", "burst-buffer"])
def test_save_seconds_counts_the_copy(tmp_path, monkeypatch, mode):
    """The manifest's save_seconds runs from the save() call, so it holds
    the device-to-host copy, in every mode."""
    real_get = jax.device_get

    def slow_get(x):
        time.sleep(0.05)
        return real_get(x)
    monkeypatch.setattr(jax, "device_get", slow_get)
    t = tree()
    copy_s = 0.05 * len(jax.tree.leaves(t))
    fast = tmp_path / "bb" if mode == "burst-buffer" else None
    mgr = CheckpointManager(tmp_path / "fs", n_shards=2, fast_dir=fast)
    if mode == "sync":
        mgr.save(3, t, sync=True)
    else:
        dev = StorageDevice(name="fs", bandwidth=2000, per_stream_cap=500)
        cluster = Cluster(workers=[WorkerNode(
            name="w0", cpus=2, io_executors=4, storage=dev)])
        with IORuntime(cluster, backend=RealBackend()):
            assert mgr.save(3, t)
            mgr.wait()
    manifest = json.loads(
        (tmp_path / "fs" / "step_00000003" / "MANIFEST.json").read_text())
    assert manifest["save_seconds"] >= copy_s
