"""The trace reducer gives busy, idle and per-op time as the raw events of
a trace recorded on a TPU v5e say.

``data/v5e_small.xplane.pb`` was recorded on one chip: inside a
``probe.window`` span, three rounds of five bf16 2048x2048 matmuls
(``probe.matmul``), a 50 ms host sleep (``probe.sleep``) and one
``segment_combine`` kernel call (``probe.kernel``)."""

import os
import sys
from collections import defaultdict

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

TRACE = os.path.join(HERE, "data", "v5e_small.xplane.pb")
SPANS = ("probe.window", "probe.matmul", "probe.sleep", "probe.kernel")


@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(TRACE)


@pytest.fixture(scope="module")
def summary(pd):
    return trace_reduce.reduce_trace(pd, window="probe.window", spans=SPANS)


def _raw(pd):
    """Window bounds and the device op events, read without the reducer."""

    host = next(p for p in pd.planes if p.name == "/host:CPU")
    win = next(e for line in host.lines for e in line.events
               if e.name == "probe.window")
    dev = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    assert len(dev) == 1
    ops = [e for line in dev[0].lines if line.name == "XLA Ops"
           for e in line.events]
    return win.start_ns, win.end_ns, ops


def test_the_checked_in_trace_is_small():
    assert os.path.getsize(TRACE) < 1 << 20


def test_busy_is_the_union_of_op_intervals_in_the_window(pd, summary):
    w0, w1, ops = _raw(pd)
    # a 1 us timeline of the window, marked busy where any op runs
    grid = np.zeros(int((w1 - w0) // 1000) + 1, bool)
    for e in ops:
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t > s:
            grid[int((s - w0) // 1000):int(-(-(t - w0) // 1000))] = True
    busy = grid.sum() * 1e-6
    assert summary.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert summary.busy_s == pytest.approx(busy, abs=len(ops) * 2e-6)
    assert 0 < summary.busy_s < summary.window_s


def test_time_by_op_sums_each_ops_events(pd, summary):
    w0, w1, ops = _raw(pd)
    want = defaultdict(float)
    for e in ops:
        want[e.name] += max(min(e.end_ns, w1) - max(e.start_ns, w0), 0) * 1e-9
    got = {k: v.seconds for k, v in summary.ops.items()}
    assert got.keys() == {k for k, v in want.items() if v > 0}
    # no op of this trace holds another, so own time is the whole time
    for k in got:
        assert got[k] == pytest.approx(want[k])
    assert sum(got.values()) == pytest.approx(summary.busy_s)


def test_nested_ops_count_once():
    ops = {}
    trace_reduce._own_times([(0, 100, "%while = w while(x)"),
                             (10, 30, "%fusion.1 = f fusion(x)"),
                             (40, 90, "%fusion.2 = f fusion(x)"),
                             (50, 60, "%copy.1 = c copy(x)")], ops)
    own = {trace_reduce.instruction(k): v.seconds * 1e9
           for k, v in ops.items()}
    assert own == pytest.approx({"while": 30, "fusion.1": 20,
                                 "fusion.2": 40, "copy.1": 10})
    assert trace_reduce.op_kind("%while = w while(x)") == "while"


def test_the_longest_idle_gaps_are_the_host_sleeps(summary):
    longest = summary.gaps[:3]
    assert all(label.startswith("probe.sleep") for label, _ in longest)
    assert all(0.045 < s < 0.2 for _, s in longest)
    idle = summary.window_s - summary.busy_s
    assert sum(s for _, s in summary.gaps) <= idle + 1e-9


def test_the_kernel_call_and_its_bytes_are_read_from_the_trace(summary):
    import hlo_bytes

    calls = {k: v for k, v in summary.ops.items()
             if trace_reduce.instruction(k).startswith("segment_combine")}
    assert len(calls) == 1
    (text, st), = calls.items()
    assert st.calls == 3 and st.seconds > 0
    assert trace_reduce.op_kind(text) == "custom-call"
    # f32[1,4096] <- s32[64], s32[64], s32[512,128], f32[1,512,128]
    assert hlo_bytes.call_bytes(text) == 4 * (4096 + 64 + 64 + 2 * 65536)
