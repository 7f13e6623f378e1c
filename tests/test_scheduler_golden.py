"""Golden decision digests for the I/O scheduler on the simulator.

Each recipe below drives one branch of the grant path (static and auto
``storageBW``, learning then steady, tier placement, lifecycle capacity,
auto-prefetch, co-tenant interference with drift, device failure with
retry and re-drain, compute under ``computingUnits``, a deep event heap)
through ``SimBackend`` with fixed seeds. The run's decisions are reduced
to two sha256 digests:

* ``log``: every launch (tid, signature, worker, device, granted
  bandwidth, start) in launch order, then every task's final placement
  (tid, signature, state, retries, worker, device, granted bandwidth,
  start, end), times rounded to 1e-9;
* ``totals``: makespan, the simulator's I/O and compute totals, and the
  bytes written per device, rounded the same way.

The digests were recorded at commit b95e466 and must not change: a
refactor of the scheduler or the simulator that alters any placement,
bandwidth grant or event time fails here. Each recipe also runs traced,
which must leave every decision identical to the untraced run.
"""
from __future__ import annotations

import hashlib
import itertools
import json

import pytest

from repro.core import (BurstyTraffic, Cluster, DriftConfig, FailureSchedule,
                        IORuntime, LifecycleConfig, SimBackend, StorageDevice,
                        WorkerNode, constraint, io, task)
from repro.core.task import TaskInstance


def _r(x: float) -> str:
    return f"{x:.9f}"


class _GrantLog(SimBackend):
    """The simulator, recording each launch as the scheduler grants it
    (every attempt of a retried task, on the device it was given)."""

    def __init__(self):
        super().__init__()
        self.grants: list[tuple] = []

    def launch(self, task, worker):
        dev = task.device.name if task.device is not None else None
        self.grants.append((task.tid, task.defn.signature, worker.name, dev,
                            _r(task.granted_bw), _r(self.clock)))
        super().launch(task, worker)


def _digest(obj) -> str:
    blob = json.dumps(obj, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def _run(recipe, traced: bool) -> dict:
    TaskInstance._ids = itertools.count()
    backend = _GrantLog()
    cluster, kw, body = recipe()
    with IORuntime(cluster, backend=backend, trace=traced, **kw) as rt:
        body(rt)
        rt.barrier(final=True)
    if traced:
        assert rt.recorder is not None and rt.recorder.events
    tasks = []
    for t in sorted(rt.graph.tasks.values(), key=lambda t: t.tid):
        tasks.append((t.tid, t.defn.signature, t.state.value, t.retries,
                      t.worker.name if t.worker is not None else None,
                      t.device.name if t.device is not None else None,
                      _r(t.granted_bw), _r(t.start_time), _r(t.end_time)))
    stats = rt.stats()
    totals = {k: _r(stats[k]) for k in (
        "makespan", "io_busy_time", "compute_busy_time", "overlap_time",
        "total_io_mb", "peak_io_mbs")}
    totals["n_tasks"] = stats["n_tasks"]
    totals["devices"] = {name: _r(d["bytes_written"])
                         for name, d in stats["devices"].items()}
    assert backend.grants, "the recipe launched nothing"
    return {"log": _digest([backend.grants, tasks]),
            "totals": _digest(totals)}


# ------------------------------------------------------------------ recipes
def static_one_tier():
    """Static storageBW writes of three sizes on one tier of node SSDs."""
    cluster = Cluster.make(n_workers=2, cpus=4, io_executors=8,
                           device_bw=200.0, per_stream_cap=20.0)

    @constraint(storageBW=40)
    @io
    @task(returns=1)
    def wr(i):
        pass

    @constraint(storageBW=90)
    @io
    @task(returns=1)
    def big(i):
        pass

    def body(rt):
        for i in range(24):
            wr(i, io_mb=30.0 + 10.0 * (i % 4))
            if i % 3 == 0:
                big(i, io_mb=120.0)
    return cluster, {}, body


def auto_ckpt_shaped():
    """The checkpoint path on the simulator: a step chain, loader fetches
    two steps ahead, and three saves of 8 auto-constrained shard writes
    plus a commit that waits on them. The first save meets the learning
    tuner, the later ones its steady choice."""
    dev = StorageDevice(name="ckpt-fs", bandwidth=2000.0,
                        per_stream_cap=500.0)
    cluster = Cluster(workers=[WorkerNode(name="host0", cpus=4,
                                          io_executors=8, storage=dev)])

    @task(returns=1)
    def step(prev, batch, i):
        pass

    @io
    @task(returns=1)
    def fetch(i):
        pass

    @constraint(storageBW="auto", maxRetries=2)
    @io
    @task(returns=1)
    def shard(state, j):
        pass

    @io
    @task(returns=1)
    def commit(i, frags):
        pass

    def body(rt):
        prev = None
        batches = {i: fetch(i, io_mb=2.0, duration=0.01) for i in range(2)}
        for i in range(12):
            batches[i + 2] = fetch(i + 2, io_mb=2.0, duration=0.01)
            prev = step(prev, batches.pop(i), i, duration=0.5)
            if i in (2, 6, 10):
                frags = [shard(prev, j, io_mb=540.0) for j in range(8)]
                commit(i, frags, io_mb=0.01)
    return cluster, {}, body


def auto_min_max_delta():
    """An auto(min,max,delta) constraint: learning walks 4, 8, 16, 32."""
    cluster = Cluster.make(n_workers=3, cpus=4, io_executors=16,
                           device_bw=300.0, per_stream_cap=12.0)

    @constraint(storageBW="auto(4,32,2)")
    @io
    @task(returns=1)
    def ck(i):
        pass

    def body(rt):
        for i in range(60):
            ck(i, io_mb=25.0 + (i % 5) * 5.0)
    return cluster, {}, body


def tiered_auto_placement():
    """No tier given on a three-tier cluster: static writes fill the SSDs
    and spill to the burst buffer and the FS; auto writes learn on the
    fastest tier."""
    cluster = Cluster.make_tiered(n_workers=2, cpus=4, io_executors=12,
                                  ssd_bw=200.0, ssd_stream_cap=20.0,
                                  bb_bw=400.0, bb_stream_cap=40.0,
                                  fs_bw=150.0, fs_stream_cap=10.0)

    @constraint(storageBW=80)
    @io
    @task(returns=1)
    def wr(i):
        pass

    @constraint(storageBW="auto")
    @io
    @task(returns=1)
    def ck(i):
        pass

    def body(rt):
        for i in range(30):
            wr(i, io_mb=60.0)
            ck(i, io_mb=40.0)
    return cluster, {}, body


def _ssd_fs(ssd_capacity_gb=None, fs_bw=200.0):
    fs = StorageDevice(name="shared-fs", bandwidth=fs_bw,
                       per_stream_cap=100.0, tier="fs")
    ssd = StorageDevice(name="w0-ssd", bandwidth=1000.0,
                        per_stream_cap=400.0, tier="ssd",
                        capacity_gb=ssd_capacity_gb)
    return Cluster(workers=[WorkerNode(name="w0", cpus=4, io_executors=8,
                                       tiers=[ssd, fs])])


def lifecycle_lru():
    """A small SSD under watermark eviction: LRU drains cold objects to the
    FS in the shadow of the step chain; the first object is pinned."""
    cluster = _ssd_fs(ssd_capacity_gb=0.375)

    @task(returns=1)
    def step(prev, gate, i):
        pass

    @constraint(storageBW=300)
    @io
    @task(returns=1)
    def wr(x, i):
        pass

    def body(rt):
        prev = gate = None
        for i in range(10):
            prev = step(prev, gate, i, duration=2.0)
            f = wr(prev, i, io_mb=100.0)
            if i == 0:
                rt.pin(f)
            gate = f
    return cluster, {}, body


def auto_prefetch_external():
    """Inputs registered on the FS are staged to the SSD by auto-prefetch
    ahead of the compute chain that reads them."""
    cluster = _ssd_fs(ssd_capacity_gb=8.0)

    @task(returns=1)
    def train(prev, shard, i):
        pass

    def body(rt):
        shards = [rt.external_data(f"s{i}", 200.0, "fs") for i in range(6)]
        prev = None
        for i, s in enumerate(shards):
            prev = train(prev, s, i, duration=1.0)
    return cluster, {"lifecycle": LifecycleConfig(auto_prefetch=True)}, body


def bursty_interference_drift():
    """A seeded bursty co-tenant on the burst buffer; drift monitoring and
    the measured tier objective move auto writes between bb and fs, and
    static writes pinned to bb wait while a tuner calibrates it."""
    bb = StorageDevice(name="bb", bandwidth=800.0, per_stream_cap=80.0,
                       tier="bb")
    fs = StorageDevice(name="fs", bandwidth=300.0, per_stream_cap=30.0,
                       tier="fs")
    cluster = Cluster(workers=[
        WorkerNode(name=f"w{i}", cpus=4, io_executors=16, tiers=[bb, fs])
        for i in range(2)])
    interf = [("bb", BurstyTraffic(seed=7, on_mean=3.0, off_mean=2.0,
                                   streams=60, bw=400.0))]
    kw = {"interference": interf, "tier_objective": True,
          "drift": DriftConfig(window=8, min_observations=4, threshold=1.5)}

    @constraint(storageBW="auto")
    @io
    @task(returns=1)
    def ck(i):
        pass

    @constraint(storageBW=60, tier="bb")
    @io
    @task(returns=1)
    def log(i):
        pass

    def body(rt):
        for i in range(160):
            ck(i, io_mb=40.0)
            if i % 8 == 0:
                log(i, io_mb=30.0)
    return cluster, kw, body


def failure_retry_redrain():
    """w0's burst buffer fails mid-write: its static writes retry on w1's,
    and the learning writes on it are un-admitted from their epoch and
    wait for it to heal. Then the FS fails and heals, so a shard whose
    durable copy died with it is re-drained from the burst buffer."""
    fs = StorageDevice(name="shared-fs", bandwidth=200.0,
                       per_stream_cap=100.0, tier="fs")
    cluster = Cluster(workers=[
        WorkerNode(name=f"w{i}", cpus=4, io_executors=8,
                   tiers=[StorageDevice(name=f"w{i}-bb", bandwidth=800.0,
                                        per_stream_cap=200.0, tier="bb"),
                          fs])
        for i in range(2)])
    sched = FailureSchedule([(1.0, "w0-bb", "offline"),
                             (4.0, "w0-bb", "healthy"),
                             (9.0, "fs", "offline"), (12.0, "fs", "healthy")])
    kw = {"failures": sched,
          "lifecycle": LifecycleConfig(enabled=True, auto_prefetch=False)}

    @constraint(storageBW=50, maxRetries=3)
    @io
    @task(returns=1)
    def wr(i):
        pass

    @constraint(storageBW="auto", maxRetries=3)
    @io
    @task(returns=1)
    def ck(i):
        pass

    @task(returns=1)
    def gate(i):
        pass

    @constraint(maxRetries=2)
    @io
    @task(returns=1)
    def shard(x):
        pass

    def body(rt):
        for i in range(6):
            wr(i, io_mb=200.0)
        for i in range(4):
            ck(i, io_mb=120.0)
        late = shard(gate(0, duration=6.0), io_mb=64.0, storage_tier="bb")
        rt.drain(late, "fs", io_mb=64.0)
        gate(1, duration=16.0)
    return cluster, kw, body


def compute_and_io():
    """Compute tasks under computingUnits 1, 2 and 4 feed static and
    unconstrained writes; data locality orders the I/O candidates, and
    some writes finish at the same instant as a compute task."""
    cluster = Cluster.make(n_workers=3, cpus=6, io_executors=4,
                           device_bw=160.0, per_stream_cap=16.0)
    cluster.shared_workdir = False

    @task(returns=1)
    def small(i):
        pass

    @constraint(computingUnits=2)
    @task(returns=1)
    def mid(x, i):
        pass

    @constraint(computingUnits=4)
    @task(returns=1)
    def wide(x, y, i):
        pass

    @constraint(storageBW=32)
    @io
    @task(returns=1)
    def wr(x, i):
        pass

    @io
    @task(returns=1)
    def log(x, i):
        pass

    @io
    @task(returns=1)
    def tag(x, i):
        pass

    def body(rt):
        for i in range(18):
            a = small(i, duration=0.5 + 0.25 * (i % 3))
            b = mid(a, i, duration=1.0)
            c = wide(a, b, i, duration=1.5)
            wr(b, i, io_mb=48.0)
            log(c, i, io_mb=4.0)
            t = tag(b, i, io_mb=1.0, duration=1.5)
            mid(c, 100 + i, duration=0.5)
            mid(t, 200 + i, duration=0.5)
    return cluster, {}, body


def deep_heap():
    """1 000 tasks: 200 chains of a compute stage, two auto writes, a
    static write and an unconstrained one, so hundreds of events are in
    the heap at once and estimates are refreshed on every release."""
    cluster = Cluster.make(n_workers=4, cpus=8, io_executors=24,
                           device_bw=450.0, per_stream_cap=8.0)

    @task(returns=1)
    def stage(i):
        pass

    @constraint(storageBW="auto")
    @io
    @task(returns=1)
    def ck_auto(x, i):
        pass

    @constraint(storageBW=24)
    @io
    @task(returns=1)
    def ck_static(x, i):
        pass

    @io
    @task(returns=1)
    def note(x, i):
        pass

    def body(rt):
        for i in range(200):
            r = stage(i, duration=1.0 + (i % 7) * 0.25)
            ck_auto(r, i, io_mb=40.0)
            ck_auto(r, -i, io_mb=20.0)
            ck_static(r, i, io_mb=30.0 + (i % 3) * 10.0)
            note(r, i, io_mb=1.0)
    return cluster, {}, body


RECIPES = {f.__name__: f for f in (
    static_one_tier, auto_ckpt_shaped, auto_min_max_delta,
    tiered_auto_placement, lifecycle_lru, auto_prefetch_external,
    bursty_interference_drift, failure_retry_redrain, compute_and_io,
    deep_heap)}

#: recorded at commit b95e466 (see the module docstring)
GOLDEN = {
    "static_one_tier": {
        "log": "5fb8e852fc2d4776a2def936554d61503d576e8cdabc6badf0134cdfc55a696e",
        "totals": "f7a79cb8e348f6f3e9b65a97cf916f74ffd167632731f3f1d85e259e1e0ce97d"},
    "auto_ckpt_shaped": {
        "log": "27390c5974112f1d0184755290facb993d07497b28570f7c13130515782b3c9f",
        "totals": "2f93e1605e4c9a058c8748f8861c1a3b369c0c98725f14bd0b8ab6ebc42faea0"},
    "auto_min_max_delta": {
        "log": "c197469ec0f0a98841091fd642cd331f5d1a286a7d1c70399651ef6385cffa24",
        "totals": "d786be4da931c430405b316a6d2eaa4468d3c828ff8ae704735134d263b2b6c4"},
    "tiered_auto_placement": {
        "log": "67c49f0ab972e3ff9a29b3ab92b63cfa8f4f402aee9c03149866e3d89edbdc82",
        "totals": "ab2ffbb5d78c939626460603c933e5745bca89968da9d97ad48fbc938368fa07"},
    "lifecycle_lru": {
        "log": "0fde681310d3db0073b269e26fafc9f5ce2b7c82d24437e7794dcf4b765889e7",
        "totals": "4221b00351e720a51328aab7d8dbb484e5c6f0d9e5f3c65850cc759f1bb80c5a"},
    "auto_prefetch_external": {
        "log": "2e7d6ae243a7c0613de186cc397a5499e1f2194724a3efe07570ed422f38245c",
        "totals": "9ef274780a9e9f33d91bfa3b9f4ab3912db07213f01c7d9185867f11f4df87ab"},
    "bursty_interference_drift": {
        "log": "6c49be811c56f131454074634aedddd435acec42a338f879ecd60952ec51f61e",
        "totals": "39cd8deff3d72fc02156d5341d6608c0b984ceabb1b683e95fce70436d5b446f"},
    "failure_retry_redrain": {
        "log": "0ca44348e3889cea49ead329976ea5fedb75ccaed18af0ebccc455f5eb8cfa11",
        "totals": "7f4839eb796143b541aef32c4240e6869491fd1bd0e1f0505606861bf8a65cf4"},
    "compute_and_io": {
        "log": "ef7044244d703fa0c793c06c83bac6b95da42163509366e7a1341c289ca8af5b",
        "totals": "1090a5e54e0b5ea2625ccfa6bec238c2625797593a12a452892db5c5a9088bbd"},
    "deep_heap": {
        "log": "4e4835d634900784fa4bf176a97191037458f9b212392ea927385f8d37e28ac4",
        "totals": "9be5590f7f49915ab838af29b46baff5f9620501b35aa9a5abfed217519d6cc0"},
}


@pytest.mark.parametrize("traced", [False, True],
                         ids=["untraced", "traced"])
@pytest.mark.parametrize("name", list(RECIPES))
def test_decisions_match_golden(name, traced):
    got = _run(RECIPES[name], traced)
    if traced:
        # the recorder only reads: the same recipe untraced decides alike
        assert got == _run(RECIPES[name], False)
    assert got == GOLDEN[name]
