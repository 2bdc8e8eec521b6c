"""benchmark/trace.py on hand-made intervals and on a small profiler
trace recorded on the CPU (data/cpu_trace.xplane.pb, made by
data/make_cpu_trace.py).  The CPU has no device plane, so the test
names the executor thread's line as the device's operations."""

import os

import pytest

from benchmark import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")
EXEC_LINE = "tf_XLAPjRtCpuClient"


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 12), (20, 25), (24, 30), (40, 40)]
    assert T.union(iv) == [(0, 12), (20, 30)]
    assert T.covered(iv) == 22
    assert T.gaps(iv, -5, 35) == [(-5, 0), (12, 20), (30, 35)]
    assert T.gaps(iv, 2, 22) == [(12, 20)]
    spans = [("a", 10, 14), ("b", 14, 21)]
    assert T.label_gap((12, 20), spans) == "b"
    assert T.label_gap((100, 200), spans) == "no host span"


@pytest.fixture(scope="module")
def reduced():
    # the window is the replay.window span: 148474 + 2204032 ns
    return T.reduce(
        DATA, "replay.window", device_prefix="/host:CPU",
        ops_line=EXEC_LINE, modules_line=EXEC_LINE,
    )


def test_busy_union_and_idle_share(reduced):
    # union of the executor line's events inside the window, by hand:
    # 231522 + 462015 + 227303 + 255131 + 59 + 207130 + 355276
    assert reduced.n_devices == 1
    assert reduced.window_ns == 2204032.0
    assert reduced.busy_ns == 1738436.0
    assert reduced.idle_share == pytest.approx(1 - 1738436 / 2204032)


def test_named_program_time(reduced):
    assert reduced.module_ns["wrapped_sine"] == 231522 + 227303 + 207130
    assert reduced.module_count["wrapped_sine"] == 3
    assert reduced.module_ns["dot_general.1"] == 255131


def test_idle_gaps_labelled_by_host_span(reduced):
    name, seconds = reduced.idle_gaps[0]
    assert (name, seconds) == ("replay.dispatch", 220369 / 1e9)
    labels = dict((round(s * 1e9), n) for n, s in reduced.idle_gaps)
    assert labels[117604] == "replay.dispatch"
    assert labels[32123] == "replay.drain"
    b = T.breakdown(reduced)
    assert b["idle_gaps"][0] == ["replay.dispatch", 220369 / 1e9]
    assert len(b["device_ops"]) <= 10
