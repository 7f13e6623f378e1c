"""Program spans (repro.obs.spans): kept exactly while a JAX profiler trace
is collected, parented across the I/O runtime's threads, and on the
profiler's clock."""
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import (Cluster, IORuntime, RealBackend, StorageDevice,
                        WorkerNode, io, task)
from repro.data import pipeline
from repro.obs import spans

N_SHARDS = 6        # more shards than leaves: two stay empty


def tree():
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones((4,), jnp.bfloat16),
            "opt": {"count": jnp.zeros((), jnp.int32),
                    "m": jnp.full((2, 2), 0.5)}}


def cluster():
    dev = StorageDevice(name="fs", bandwidth=2000, per_stream_cap=500)
    return Cluster(workers=[WorkerNode(name="w0", cpus=2, io_executors=4,
                                       storage=dev)])


def save_and_load(directory):
    """One async save, waited for, and three loader steps."""
    mgr = CheckpointManager(directory, n_shards=N_SHARDS)
    corpus = pipeline.SyntheticCorpus(500, 8, 2, seed=3)
    loader = pipeline.PrefetchLoader(corpus, depth=2)
    with IORuntime(cluster(), backend=RealBackend()):
        assert mgr.save(7, tree())
        mgr.wait()
        for step in range(3):
            loader.get(step)


def xplane_events(directory):
    """{span_id: start_ns} of the host events a profiler trace wrote."""
    from jax.profiler import ProfileData
    path = next(directory.rglob("*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                ids = [v for k, v in e.stats if k == "span_id"]
                if ids:
                    out[int(ids[0])] = e.start_ns
    return out


@pytest.fixture(scope="module")
def traced_save(tmp_path_factory):
    """Records and xplane events of a save and a loader run under the
    profiler."""
    base = tmp_path_factory.mktemp("traced_save")
    spans.clear()
    with jax.profiler.trace(str(base / "trace")):
        save_and_load(base / "ckpt")
    recs = spans.records()
    spans.clear()
    return recs, xplane_events(base / "trace")


def named(recs, name):
    return [r for r in recs if r.name.split(":", 1)[0] == name]


def test_no_records_without_profiler(tmp_path):
    spans.clear()
    assert not spans.enabled()
    save_and_load(tmp_path)
    assert spans.records() == []
    with spans.span("x", n=1):
        spans.count("n", 1)
    assert spans.records() == []


def test_parent_and_child_on_one_thread(tmp_path):
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("outer", k=1):
            with spans.span("inner"):
                spans.count("n", 2)
                spans.count("n", 3)
                assert spans.current() is not None
            spans.tag("late", "yes")
    outer, inner = spans.records()
    spans.clear()
    assert (outer.name, inner.name) == ("outer", "inner")
    assert outer.parent is None and inner.parent == outer.id
    assert inner.counts == {"n": 5}
    assert outer.counts == {"k": 1, "late": "yes"}
    assert outer.thread == inner.thread == threading.get_native_id()
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert 0 <= inner.cpu_ns and not inner.open


def test_save_tree(traced_save):
    recs, _ = traced_save
    assert all(not r.open for r in recs)
    (save,) = named(recs, "ckpt.save")
    assert save.counts == {"step": 7, "mode": "flat"}
    children = [r for r in recs if r.parent == save.id]
    assert [r.name for r in children] == [
        "ckpt.snapshot", "ckpt.plan", "ckpt.submit", "ckpt.gc"]
    for c in children:
        assert c.thread == save.thread
        assert save.start_ns <= c.start_ns <= c.end_ns <= save.end_ns
    leaves = jax.tree.leaves(tree())
    assert children[0].counts["bytes"] == sum(a.nbytes for a in leaves)
    n_written = min(N_SHARDS, len(leaves))
    assert children[2].counts["tasks"] == n_written + 1    # and the commit
    shards = named(recs, "ckpt.shard")
    assert len(shards) == n_written
    assert sum(s.counts["bytes"] for s in shards) == children[0].counts["bytes"]
    for s in shards:
        assert {"serialize_ns", "write_ns", "fsync_ns"} <= set(s.counts)
        assert s.counts["copied_bytes"] == 0    # the snapshot is C order
        (fsync,) = [r for r in recs if r.parent == s.id]
        assert fsync.name == "ckpt.shard.fsync"
        assert fsync.seconds * 1e9 <= s.counts["fsync_ns"]
    (wait,) = named(recs, "ckpt.wait")
    assert wait.parent is None


def test_cross_thread_parent(traced_save):
    recs, _ = traced_save
    (submit,) = named(recs, "ckpt.submit")
    queued = [r for r in named(recs, "io.queued") if r.parent == submit.id]
    runs = [r for r in named(recs, "io.run") if r.parent == submit.id]
    n_written = len(named(recs, "ckpt.shard"))
    assert len(queued) == n_written + 1                    # and the commit
    assert {q.counts["tid"] for q in queued} == \
        {r.counts["tid"] for r in runs}
    by_tid = {r.counts["tid"]: r for r in runs}
    for q in queued:
        run = by_tid[q.counts["tid"]]
        assert q.name.replace("io.queued:", "io.run:") == run.name
        assert q.end_ns <= run.start_ns
        assert run.thread != submit.thread
    for s in named(recs, "ckpt.shard"):
        run = next(r for r in recs if r.id == s.parent)
        assert run.parent == submit.id and run.thread == s.thread
    assert {r.id for r in spans.subtree(recs, [submit.id])} >= \
        {r.id for r in named(recs, "ckpt.shard")}


def test_loader_spans(traced_save):
    recs, _ = traced_save
    gets = named(recs, "loader.get")
    assert len(gets) == 3
    for g in gets:
        assert g.counts["ready"] in (0, 1)
        (wait,) = [r for r in recs if r.parent == g.id
                   and r.name == "loader.wait"]
        assert g.start_ns <= wait.start_ns <= wait.end_ns <= g.end_ns
        fetches = [r for r in named(recs, "io.queued") if r.parent == g.id]
        assert all(r.name == "io.queued:_fetch_task" for r in fetches)
    assert len(named(recs, "io.queued")) >= len(named(recs, "ckpt.shard")) + 3


def test_lock_wait_charged_to_waiting_span(tmp_path):
    @io
    @task(returns=1)
    def noop():
        return 1

    held, release = threading.Event(), threading.Event()
    with IORuntime(cluster(), backend=RealBackend()) as rt:
        def hold():
            with rt.lock:
                held.set()
                release.wait(5)

        spans.clear()
        with jax.profiler.trace(str(tmp_path)):
            with spans.span("quiet"), spans.locked(threading.Lock()):
                pass
            holder = threading.Thread(target=hold)
            holder.start()
            assert held.wait(5)
            threading.Timer(0.05, release.set).start()
            with spans.span("waiter"):
                fut = noop()
            holder.join(5)
            assert not holder.is_alive()
            rt.wait_on(fut)
        recs = spans.records()
        spans.clear()
    (quiet,) = named(recs, "quiet")
    (waiter,) = named(recs, "waiter")
    assert "lock_wait_ns" not in quiet.counts
    assert waiter.counts["lock_wait_ns"] >= 40e6
    assert waiter.counts["lock_wait_ns"] <= waiter.seconds * 1e9


def test_records_line_up_with_the_xplane(traced_save):
    recs, events = traced_save
    annotated = [r for r in recs if r.cpu_ns is not None]
    assert len(annotated) >= 15
    offsets = [events[r.id] - r.start_ns for r in annotated]
    assert max(offsets) - min(offsets) < 1e6


def test_buffer_bound_counts_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "CAPACITY", 2)
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(5):
            with spans.span("s"):
                pass
        spans.record("late", time.perf_counter_ns(), time.perf_counter_ns(),
                     None)
    assert len(spans.records()) == 2 and spans.dropped() == 4
    spans.clear()
    assert spans.records() == [] and spans.dropped() == 0
