"""The program's scopes and spans are read from a trace recorded on a TPU
v5e, and the per-operator readers fall silent on a trace without them.

``data/v5e_program.xplane.pb`` was recorded on one chip by
``record_program_trace.py``: inside a ``window`` span, one PPR row-table job
(3 iterations, Graph500 scale 12) and one PageRank job (10 supersteps,
scale 10), each warmed up first, each in a ``job`` span.
``data/v5e_small.xplane.pb`` (``test_trace_reducer.py``) predates the
program's scopes and spans."""

import os
import shutil
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import program_trace  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

SCOPED = os.path.join(HERE, "data", "v5e_program.xplane.pb")
UNSCOPED = os.path.join(HERE, "data", "v5e_small.xplane.pb")
READERS = ("gather.device_s", "exchange.device_s", "join.device_s",
           "groupby.device_s", "fixpoint.trace_s")
ITERATIONS = 3 + 10


@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(SCOPED)


@pytest.fixture(scope="module")
def summary(pd):
    return trace_reduce.reduce_trace(pd)


@pytest.fixture(scope="module")
def pt(summary):
    return program_trace.analyse(SCOPED, summary)


def _readers():
    return {name: run.load_module(run._find(run.ROOT, "metrics", name, ".py"))
            for name in READERS}


def _read_all(monkeypatch, tmp_path, trace, window, iterations):
    """Every new reader's number over ``trace``, as a run would read it."""

    shutil.copy(trace, tmp_path / "t.xplane.pb")
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(program_trace, "WINDOW", window)
    ctx = SimpleNamespace(
        trace=trace_reduce.reduce_trace(trace_reduce.load(trace),
                                        window=window),
        iterations=iterations)
    return {name: r.read(ctx) for name, r in _readers().items()}


def test_the_checked_in_trace_is_small():
    assert os.path.getsize(SCOPED) < 1 << 20


def test_the_metadata_reader_names_the_ops_as_profile_data_does(pd):
    scopes = program_trace.instruction_scopes(SCOPED)
    ops, other = set(), set()
    for plane in pd.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                names = {ev.name for ev in line.events}
                (ops if line.name == trace_reduce.OPS_LINE else other) \
                    .update(names)
    assert ops and ops <= set(scopes)
    assert {t for t, tf_op in scopes.items() if tf_op} <= ops | other
    # the engine's scopes reach the device (the ops of programs compiled
    # one primitive at a time, outside any step, carry none)
    assert any(program_trace.program_scopes(scopes[t]) for t in ops)


def test_scoped_and_unscoped_seconds_add_up_to_busy(pt, summary):
    total = sum(pt.by_scopes.values()) / pt.devices
    assert total == pytest.approx(summary.busy_s, rel=0.01)
    by_op = pt.by_operator()
    assert sum(by_op.values()) == pytest.approx(total)
    for name in ("join", "groupby", "gather", "exchange"):
        assert by_op[name] > 0
    assert 0 < pt.unscoped_share() < 0.01


def test_every_new_reader_reads_the_scoped_trace(monkeypatch, tmp_path, pt):
    got = _read_all(monkeypatch, tmp_path, SCOPED, "window", ITERATIONS)
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["join.device_s"] > 0 and got["gather.device_s"] > 0
    assert got["join.device_s"] == pytest.approx(
        pt.operator_s("join") / ITERATIONS)
    assert got["fixpoint.trace_s"] > 0


def test_every_new_reader_is_silent_on_an_unscoped_trace(monkeypatch,
                                                          tmp_path):
    got = _read_all(monkeypatch, tmp_path, UNSCOPED, "probe.window", 3)
    assert got == dict.fromkeys(READERS)


def test_a_trace_without_the_window_span_reads_as_nothing(monkeypatch,
                                                          tmp_path):
    shutil.copy(UNSCOPED, tmp_path / "t.xplane.pb")
    assert program_trace.read(tmp_path) is None
    assert program_trace.read(tmp_path / "empty") is None


def test_spans_count_what_the_program_did(pt):
    # the row-table steps are memoized; PageRank's loop is traced anew
    assert pt.job_trace_spans == [0, 1]
    assert pt.spans["fixpoint.trace"][0] == 1
    assert pt.spans["fixpoint.device_loop"][0] == 1
    assert pt.spans["fixpoint.iteration"][0] == 3
    for name in ("fixpoint.dispatch", "fixpoint.wait", "fixpoint.converged"):
        assert pt.spans[name][0] == 3
    for name in ("executor.prelude", "executor.phase_init",
                 "executor.finals", "executor.overflow_check",
                 "executor.result"):
        assert pt.spans[name][0] == 1


def test_idle_gaps_are_labelled_by_program_spans(pt, summary):
    assert pt.gaps
    labels = {label for label, _ in pt.gaps}
    assert any(label.startswith(program_trace.SPAN_PREFIXES)
               for label in labels)
    idle = summary.window_s - summary.busy_s
    assert sum(s for _, s in pt.gaps) <= idle + 1e-6


def test_scope_paths_name_the_innermost_operator():
    tf_op = ("jit(<lambda>)/rule.R2/groupby/apply/join/expand/sort/"
             "jit(lexsort)/sort:")
    scopes = program_trace.program_scopes(tf_op)
    assert scopes == ("rule.R2", "groupby", "apply", "join", "expand",
                      "sort")
    assert program_trace.operator_of(scopes) == "join"
    assert program_trace.operator_of(("exchange", "combine")) == "exchange"
    assert program_trace.program_scopes("jit(<lambda>)/gather:") == ()
    assert program_trace.operator_of(()) is None
