"""Checkpoint substrate: roundtrip, atomic commit, async via runtime,
elastic restore."""
import json
import os
import time
import tracemalloc

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.checkpoint import serializer
from repro.checkpoint.serializer import read_shard, write_shard
from repro.core import Cluster, IORuntime, RealBackend, StorageDevice, WorkerNode
from repro.obs import spans


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


def traced_write(path, entries):
    """write_shard under the profiler: (fragment, the ckpt.shard counts)."""
    spans.clear()
    with jax.profiler.trace(str(path.parent / "trace")):
        frag = write_shard(path, entries)
    (shard,) = [r for r in spans.records() if r.name == "ckpt.shard"]
    spans.clear()
    return frag, shard.counts


LEAVES = {
    "f32_2d": lambda: np.arange(12, dtype=np.float32).reshape(3, 4),
    "bf16": lambda: np.linspace(-2, 2, 10).astype(ml_dtypes.bfloat16),
    "scalar": lambda: np.asarray(np.int32(7)),
    "transposed": lambda: np.arange(24, dtype=np.float32).reshape(4, 6).T,
    "transposed_3d": lambda: np.arange(30, dtype=np.float32)
    .reshape(5, 3, 2).transpose(2, 1, 0),
    "strided_bf16": lambda: np.linspace(-1, 1, 40)
    .astype(ml_dtypes.bfloat16)[::3],
    "int32": lambda: np.arange(-5, 5, dtype=np.int32).reshape(2, 5),
}


@pytest.mark.parametrize("stage_bytes", [serializer.STAGE_BYTES, 8],
                         ids=["stage_default", "stage_8B"])
@pytest.mark.parametrize("case", sorted(LEAVES))
def test_write_shard_matches_tobytes_format(tmp_path, monkeypatch, case,
                                            stage_bytes):
    """Shard files and manifest fragments are what the tobytes format
    wrote, byte for byte, and read back to the same arrays; only a leaf
    that is not C-contiguous is copied, in blocks of the staging buffer
    (8 bytes: split down to single elements)."""
    monkeypatch.setattr(serializer, "STAGE_BYTES", stage_bytes)
    leaf = LEAVES[case]()
    entries = [("x", leaf), ("y", np.arange(5, dtype=np.float32))]
    frag, counts = traced_write(tmp_path / "shard.bin", entries)

    blobs = [np.asarray(a).tobytes() for _, a in entries]
    assert (tmp_path / "shard.bin").read_bytes() == b"".join(blobs)
    offsets = np.cumsum([0] + [len(b) for b in blobs])
    assert frag == {
        "file": "shard.bin", "total_bytes": int(offsets[-1]),
        "entries": {k: {"shape": list(a.shape), "dtype": str(a.dtype),
                        "offset": int(o), "nbytes": len(b)}
                    for (k, a), o, b in zip(entries, offsets, blobs)}}

    out = {}
    read_shard(tmp_path / "shard.bin", frag, out)
    for k, a in entries:
        assert out[k].dtype == a.dtype and out[k].shape == a.shape
        np.testing.assert_array_equal(out[k], a)
    assert counts["bytes"] == offsets[-1]
    assert counts["copied_bytes"] == \
        (0 if leaf.flags.c_contiguous else leaf.nbytes)
    assert (counts["copied_bytes"] > 0) == (case in (
        "transposed", "transposed_3d", "strided_bf16"))


@pytest.mark.parametrize("layout", ["contiguous", "transposed"])
def test_write_shard_host_copy_is_bounded(tmp_path, layout):
    """A contiguous 64 MB leaf is written from its own buffer, with no new
    allocation near its size (the tobytes format made a 64 MB bytes); a
    transposed one is copied through the staging buffer alone."""
    leaf = np.ones((4096, 4096), np.float32)
    if layout == "transposed":
        leaf = leaf.T
    spans.clear()
    with jax.profiler.trace(str(tmp_path / "trace")):
        tracemalloc.start()
        try:
            frag = write_shard(tmp_path / "shard.bin", [("w", leaf)])
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
    (shard,) = [r for r in spans.records() if r.name == "ckpt.shard"]
    spans.clear()
    if layout == "contiguous":
        assert peak < 1 << 20
        assert shard.counts["copied_bytes"] == 0
    else:
        assert peak < serializer.STAGE_BYTES + (1 << 20)
        assert shard.counts["copied_bytes"] == leaf.nbytes
    assert frag["total_bytes"] == leaf.nbytes == 64 << 20
    assert (tmp_path / "shard.bin").stat().st_size == leaf.nbytes
