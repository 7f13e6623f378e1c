"""iolint: table-driven coverage of every diagnostic code (one minimal
failing program per code), capture-mode guarantees (no task body ever
executes), the golden zero-diagnostics check over examples/quickstart.py,
the IOSan inline sanitizer (bit-identical launch logs with the checks on,
violations reported with a trace), and the early-validation satellites
(RealBackend tier_dirs keys, TraceTraffic.from_jsonl line numbers).
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import (BurstyTraffic, Cluster, IORuntime, LifecycleConfig,
                        RealBackend, SimBackend, StorageDevice, TraceTraffic,
                        WorkerNode, constraint, io, task)
from repro.analysis import Diagnostic, SanitizerError
from repro.analysis.lint import lint_script

REPO = Path(__file__).resolve().parents[1]


def tiered(**kw):
    return Cluster.make_tiered(n_workers=2, **kw)


# --------------------------------------------------------------------------
# one minimal failing program per diagnostic code
# --------------------------------------------------------------------------
# each builder returns (runtime-after-run, expected offending task signature
# or None for config-level diagnostics); registered as
# (code, message substring, builder)
CASES = []


def case(code, substr):
    def deco(fn):
        CASES.append(pytest.param(code, substr, fn, id=code))
        return fn
    return deco


@case("IO101", "exceeds every eligible device's bandwidth")
def _io101():
    @constraint(storageBW=10**6)
    @io
    @task(returns=1)
    def over_bw(x):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        over_bw(1, io_mb=8)
    return rt, "over_bw"


@case("IO102", "not present on any worker")
def _io102():
    @constraint(tier="nvram")
    @io
    @task(returns=1)
    def bad_tier(x):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        bad_tier(1, io_mb=8)
    return rt, "bad_tier"


@case("IO103", "exceeds every worker's cpus")
def _io103():
    @constraint(computingUnits=10**4)
    @task(returns=1)
    def big_cu(x):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        big_cu(1)
    return rt, "big_cu"


@case("IO104", "lower bound")
def _io104():
    @constraint(storageBW="auto(50000,90000,1000)")
    @io
    @task(returns=1)
    def auto_min(x):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        auto_min(1, io_mb=8)
    return rt, "auto_min"


@case("IO201", "exceeds every eligible device's total capacity")
def _io201():
    @io
    @task(returns=1)
    def fat_write(x):
        pass

    with IORuntime(tiered(ssd_capacity_gb=0.001),
                   backend="capture") as rt:
        fat_write(1, io_mb=500.0, storage_tier="ssd")
    return rt, "fat_write"


@case("IO202", "the run will wedge capacity-blocked")
def _io202():
    @io
    @task(returns=1)
    def hot_write(x):
        pass

    with IORuntime(tiered(ssd_capacity_gb=0.004),
                   backend="capture") as rt:
        # each write fits a 4 MB SSD, but pinning all three (12 MB) exceeds
        # the tier's total (2 workers x 4 MB): nothing is evictable
        for i in range(3):
            rt.pin(hot_write(i, io_mb=3.0, storage_tier="ssd"))
    return rt, "hot_write"


@case("IO203", "pin without a matching unpin")
def _io203():
    @io
    @task(returns=1)
    def pinned_write(x):
        pass

    with IORuntime(tiered(ssd_capacity_gb=1.0), backend="capture") as rt:
        rt.pin(pinned_write(1, io_mb=8.0))
    return rt, "pinned_write"


@case("IO204", "durable tier")
def _io204():
    # finite fs (the default durable tier) + auto_evict: a live runtime
    # refuses this config; capture records it as a diagnostic instead
    with IORuntime(tiered(ssd_capacity_gb=1.0, fs_capacity_gb=1.0),
                   backend="capture",
                   lifecycle=LifecycleConfig(auto_evict=True)) as rt:
        pass
    return rt, None


@case("IO301", "race on path")
def _io301():
    @io
    @task(returns=1)
    def appender(path):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        appender(path="/scratch/shared.log", io_mb=4)
        appender(path="/scratch/shared.log", io_mb=4)
    return rt, "appender"


@case("IO302", "after rt.discard()")
def _io302():
    @io
    @task(returns=1)
    def temp_write(x):
        pass

    @task(returns=1)
    def late_read(x):
        pass

    with IORuntime(tiered(ssd_capacity_gb=1.0), backend="capture") as rt:
        f = temp_write(1, io_mb=4.0)
        rt.discard(f)
        late_read(f)
    return rt, "late_read"


@case("IO303", "no dependency on a producer")
def _io303():
    with IORuntime(tiered(), backend="capture") as rt:
        rt.drain(None, to_tier="fs", from_tier="ssd", io_mb=64.0)
    return rt, "tier_drain"


@case("IO304", "no ordering after shard task")
def _io304():
    @io
    @task(returns=1)
    def ckpt_shard(i):
        pass

    @io
    @task(returns=1)
    def ckpt_commit(m):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        for i in range(2):
            ckpt_shard(i, io_mb=8.0)
        ckpt_commit("manifest", io_mb=1.0)  # no shard futures passed
    return rt, "ckpt_commit"


@case("IO401", "has no seed")
def _io401():
    traffic = [("fs", BurstyTraffic(None, on_mean=2.0, off_mean=8.0,
                                    bw=100.0))]
    with IORuntime(tiered(), backend="capture", interference=traffic) as rt:
        pass
    return rt, None


@case("IO402", "unseeded RNG source")
def _io402():
    @task(returns=1)
    def entropy(x):
        import random
        return random.random()

    with IORuntime(tiered(), backend="capture") as rt:
        entropy(1)
    return rt, "entropy"


@pytest.mark.parametrize("code,substr,builder", CASES)
def test_code_fires(code, substr, builder):
    rt, offender = builder()
    diags = rt.lint()
    hits = [d for d in diags if d.code == code]
    assert hits, f"{code} not emitted; got {[str(d) for d in diags]}"
    d = hits[0]
    assert substr in d.message, str(d)
    assert d.task == offender
    if offender is None:
        assert d.tid is None


def test_lint_categories_covered():
    cats = {p.values[0][2:3] for p in CASES}
    # category "5" (failure-domains) is exercised end-to-end in
    # test_failures.py against live fault-injection runs
    assert cats == {"1", "2", "3", "4"}
    assert len(CASES) >= 12  # distinct codes, each with a dedicated case


def test_diagnostic_str_and_category():
    d = Diagnostic("IO301", "boom", task="wr", tid=7)
    assert d.category == "race/ordering"
    assert str(d) == "IO301 (race/ordering) [wr#7]: boom"
    assert Diagnostic("IO204", "cfg").category == "capacity"


# --------------------------------------------------------------------------
# capture-mode guarantees
# --------------------------------------------------------------------------
def test_capture_never_executes_task_bodies():
    ran = []

    @io
    @task(returns=1)
    def effectful(x):
        ran.append(x)
        return x * 2

    with IORuntime(tiered(), backend="capture") as rt:
        f = effectful(21, io_mb=4)
        g = effectful(f, io_mb=4)
        assert rt.wait_on(g) is None  # capture resolves futures to None
    assert ran == []
    assert rt.capture_mode


def test_capture_records_full_edges_and_zero_clock():
    @task(returns=1)
    def a():
        pass

    @task(returns=1)
    def b(x):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        f = a()
        rt.wait_on(f)      # producer resolves before the consumer submits
        g = b(f)
        rt.wait_on(g)
    cap = rt.backend.capture
    tids = [t.tid for t in cap.tasks]
    assert len(tids) == 2
    # the DONE-producer edge survives (TaskGraph.add would elide it)
    assert cap.edges[tids[1]] == {tids[0]: True}
    assert rt.stats()["makespan"] == 0.0


def test_capture_leaves_live_devices_untouched():
    cluster = tiered(ssd_capacity_gb=1.0)

    @io
    @task(returns=1)
    def wr(x):
        pass

    with IORuntime(cluster, backend="capture") as rt:
        rt.external_data("init", 200.0, "fs", pinned=True)
        rt.pin(wr(1, io_mb=64.0))
        rt.lint()
    for d in cluster.devices:
        assert d.used_mb == 0.0
        assert d.available_bw == d.bandwidth


def test_plan_context_on_live_runtime():
    @io
    @task(returns=1)
    def wr(path):
        pass

    with IORuntime(tiered(), backend=SimBackend()) as rt:
        with rt.plan() as p:
            wr(path="/x.log", io_mb=4)
            wr(path="/x.log", io_mb=4)
        assert [d.code for d in p.lint()] == ["IO301"]
        # the ambient runtime is restored: new submissions go to rt
        f = wr(path="/y.log", io_mb=4)
        assert rt.wait_on(f) is None or True
        assert len(rt.graph.tasks) == 1
        assert rt.lint() == []


def test_clean_program_zero_diagnostics():
    @task(returns=1)
    def gen(i):
        pass

    @constraint(storageBW="auto")
    @io
    @task(returns=1)
    def ck(b, i):
        pass

    with IORuntime(tiered(), backend="capture") as rt:
        for i in range(4):
            ck(gen(i), i, io_mb=8)
    assert rt.lint() == []


# --------------------------------------------------------------------------
# golden check + CLI
# --------------------------------------------------------------------------
def test_quickstart_example_lints_clean():
    diags, notes = lint_script(str(REPO / "examples" / "quickstart.py"))
    assert diags == [], [str(d) for d in diags]
    assert notes == [], notes  # runs end-to-end under capture, no guards hit


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "from repro.core import Cluster, IORuntime, constraint, io, task\n"
        "@constraint(tier='tape')\n"
        "@io\n"
        "@task(returns=1)\n"
        "def wr(x): pass\n"
        "with IORuntime(Cluster.make_tiered(n_workers=2)) as rt:\n"
        "    wr(1, io_mb=4)\n")
    clean = tmp_path / "clean.py"
    clean.write_text(
        "from repro.core import Cluster, IORuntime, task\n"
        "@task(returns=1)\n"
        "def f(x): pass\n"
        "with IORuntime(Cluster.make_tiered(n_workers=2)) as rt:\n"
        "    f(1)\n")
    r = subprocess.run([sys.executable, "-m", "repro.lint", str(dirty)],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 1
    assert "IO102" in r.stdout
    r = subprocess.run([sys.executable, "-m", "repro.lint", str(clean)],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout
    r = subprocess.run([sys.executable, "-m", "repro.lint",
                        str(tmp_path / "missing.py")],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 2


# --------------------------------------------------------------------------
# IOSan inline sanitizer
# --------------------------------------------------------------------------
def _small_workload(sanitize):
    @task(returns=1)
    def gen(i):
        pass

    @io
    @task(returns=1)
    def ck(b, i):
        pass

    cluster = tiered(ssd_capacity_gb=0.01)
    with IORuntime(cluster, backend=SimBackend(sanitize=sanitize)) as rt:
        for i in range(12):
            ck(gen(i), i, io_mb=3.0, storage_tier="ssd")
        rt.barrier(final=True)
        return list(rt.scheduler.launch_log), rt.stats()["makespan"]


def test_sanitizer_parity_bit_identical():
    from repro.core.task import TaskInstance
    import itertools
    TaskInstance._ids = itertools.count()
    log_off, mk_off = _small_workload(False)
    TaskInstance._ids = itertools.count()
    log_on, mk_on = _small_workload(True)
    assert log_on == log_off
    assert mk_on == mk_off


def test_sanitizer_catches_occupancy_corruption():
    @io
    @task(returns=1)
    def wr(i):
        pass

    be = SimBackend(sanitize=True)
    with IORuntime(tiered(ssd_capacity_gb=0.1), backend=be) as rt:
        f = wr(0, io_mb=4.0, storage_tier="ssd")
        rt.wait_on(f)
        dev = rt.cluster.workers[0].storage
        before = dev.used_mb
        dev.used_mb = dev.capacity_mb + 64.0  # corrupt: occupancy > capacity
        with pytest.raises(SanitizerError, match="occupancy"):
            be.sanitizer.check(be)
        dev.used_mb = before  # restore for the exit barrier's check


def test_sanitizer_catches_clock_regression():
    be = SimBackend(sanitize=True)
    with IORuntime(tiered(), backend=be) as rt:
        @task(returns=1)
        def f(i):
            pass
        rt.wait_on(f(0, duration=5.0))
        be.clock -= 1.0
        with pytest.raises(SanitizerError, match="went backwards"):
            be.sanitizer.check(be)
        be.clock += 1.0  # restore for the exit barrier's check


def test_sanitizer_error_carries_trace():
    be = SimBackend(sanitize=True)
    with IORuntime(tiered(), backend=be) as rt:
        @io
        @task(returns=1)
        def wr(i):
            pass
        rt.wait_on(wr(0, io_mb=2.0))
        dev = rt.cluster.workers[0].storage
        dev.active_io = -3
        with pytest.raises(SanitizerError) as exc:
            be.sanitizer.check(be)
        dev.active_io = 0  # restore for the exit barrier's check
        assert "launch" in str(exc.value)  # event trace in the report


# --------------------------------------------------------------------------
# early-validation satellites
# --------------------------------------------------------------------------
def test_real_backend_rejects_unknown_tier_dirs_key(tmp_path):
    be = RealBackend(tier_dirs={"ssd": tmp_path, "bogus": tmp_path})
    with pytest.raises(ValueError, match=r"bogus.*name no storage tier"):
        IORuntime(tiered(), backend=be)


def test_real_backend_single_tier_cluster_keys_unchecked(tmp_path):
    # on a single-tier cluster tier_dirs labels are plain directory names
    # for tier-agnostic path= movement (see test_real_backend_drain_moves_
    # file) — validation only applies when the cluster models a hierarchy
    dev = StorageDevice(name="d", bandwidth=100, per_stream_cap=50)
    cluster = Cluster(workers=[WorkerNode(name="w0", cpus=2, io_executors=2,
                                          storage=dev)])
    be = RealBackend(tier_dirs={"ssd": tmp_path, "fs": tmp_path})
    with IORuntime(cluster, backend=be):
        pass


def test_from_jsonl_reports_line_numbers():
    with pytest.raises(ValueError, match="trace line 2: invalid JSON"):
        TraceTraffic.from_jsonl(['{"t": 0, "dur": 1}', "{not json"])
    with pytest.raises(ValueError, match="trace line 1: expected a JSON "
                                         "object"):
        TraceTraffic.from_jsonl(["[1, 2, 3]"])
    with pytest.raises(ValueError, match="trace line 3: needs 't' and "
                                         "'dur'"):
        TraceTraffic.from_jsonl(['{"t": 0, "dur": 1}', "# comment",
                                 '{"t": 4}'])
    with pytest.raises(ValueError, match="trace line 1: invalid record"):
        TraceTraffic.from_jsonl(['{"t": "zero", "dur": 1}'])
