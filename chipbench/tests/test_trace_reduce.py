"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): three 20 ms host sleeps under ``loader_get``, each
followed by a jitted matrix product under ``train_step`` and its wait under
``block``."""
from pathlib import Path

import pytest

from chipbench import trace_reduce

TRACE = Path(__file__).parent / "data" / "small.xplane.pb"
SPANS = {"loader_get", "train_step", "block"}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE, SPANS)


def test_window_and_busy(reduced):
    assert reduced["n_devices"] == 1
    assert 0.06 < reduced["window_s"] < 1.0
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_idle_is_named_by_the_host_span(reduced):
    idle = dict(reduced["idle_gaps"])
    assert max(idle, key=idle.get) == "loader_get"
    assert 0.055 < idle["loader_get"] < 0.08          # three 20 ms sleeps
    assert sum(idle.values()) + reduced["busy_s"] == \
        pytest.approx(reduced["window_s"], rel=1e-6)


def test_device_ops_account_for_busy_time(reduced):
    ops = dict(reduced["device_ops"])
    assert ops and all(v > 0 for v in ops.values())
    assert sum(ops.values()) >= reduced["busy_s"] * 0.999


def test_gap_naming_takes_the_innermost_span():
    cuts, labels = trace_reduce._segments(
        [(0, 100, "outer"), (20, 40, "inner"), (60, 70, "inner2")])
    named = {}
    for name, ns in trace_reduce._name_gap(10, 80, cuts, labels):
        named[name] = named.get(name, 0) + ns
    assert named == {"outer": 40, "inner": 20, "inner2": 10}
    assert list(trace_reduce._name_gap(150, 160, cuts, labels)) == \
        [("other", 10)]


def test_an_operation_counts_its_self_time():
    ops = [("%while.1 = (s32[]{:T(128)}, f32[4]) while(...)", 0, 100),
           ("%fusion.2 = f32[4,8]{1,0:T(8,128)} fusion(...)", 10, 30),
           ("%fusion.3 = (bf16[2]{0}, f32[2]) fusion(...)", 40, 50),
           ("%copy.4 = f32[] copy(...)", 100, 120)]
    got = {trace_reduce._short(n): ns * 1e9
           for n, ns in trace_reduce._self_times(ops)}
    assert got == pytest.approx({"while.1 s32[]": 70, "fusion.2 f32[4,8]":
                                 20, "fusion.3 bf16[2]": 10,
                                 "copy.4 f32[]": 20})


def test_several_chips_read_per_chip(monkeypatch):
    """Two chips, one busy 60 ns and one 20 ns of a 100 ns window: busy
    time, each operation's time and the idle time are their means."""
    op = "%fusion.1 = f32[8]{0} fusion(...)"
    devices = {"/device:TPU:0": [(op, 10, 70)],
               "/device:TPU:1": [(op, 10, 30)]}
    host = [("window", 0, 100), ("block", 0, 100)]
    monkeypatch.setattr(trace_reduce, "read_events",
                        lambda path: (devices, host))
    out = trace_reduce.reduce("two.xplane.pb", {"block"})
    assert out["n_devices"] == 2
    assert out["busy_s"] == pytest.approx(40e-9)
    assert dict(out["device_ops"]) == pytest.approx({"fusion.1 f32[8]": 40e-9})
    assert dict(out["idle_gaps"]) == pytest.approx({"block": 60e-9})
