#!/usr/bin/env python3
"""The program's own scopes and spans in a benchmark run's profiler trace.

The engine names its device work with ``jax.named_scope`` (one scope per
logical operator and superstep stage, with physical sub-scopes inside) and
its host phases with ``jax.profiler.TraceAnnotation`` spans
(``fixpoint.*``, ``executor.*``); ``docs/optimizations.md`` lists them.  A
scope reaches the device trace as the ``tf_op`` stat of each instruction's
event metadata (the JAX name stack, e.g.
``jit(<lambda>)/rule.R2/groupby/apply/join/expand/gather:``).
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
:func:`instruction_scopes` reads them from the ``.xplane.pb`` with a
protobuf wire-format decoder of the standard library, skipping the event
lines, which ``ProfileData`` already gives.

Device time is the own time of each instruction over the ``window`` span,
as ``trace_reduce`` computes it (window clipping, nested ops counted once),
grouped by the instruction's innermost program scope.  A physical
sub-scope (:data:`SUBSCOPES`) counts toward the operator that encloses it:
``join/expand/sort`` is join time.

    python3 bench/program_trace.py [trace dir]

prints the summary of the trace a ``--trace 1`` run left (default
``<root>/.bench_trace``) as one JSON object.
"""

from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

import trace_reduce

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
WINDOW = "window"

# Scopes the engine opens, and the physical sub-scopes that count toward
# the operator or stage around them.  ``rule.<label>`` scopes are program
# scopes too.
OPERATORS = frozenset({
    "scan", "join", "cross", "antijoin", "select", "project", "extend",
    "apply", "groupby", "unnest", "materialize", "merge",
    "diff", "overflow", "gather", "exchange", "compact", "converged",
    "map", "reduce", "update",
})
SUBSCOPES = frozenset({"sort", "expand", "runs", "combine"})
SPAN_PREFIXES = ("fixpoint.", "executor.")
NO_SCOPE = "(no program scope)"
NO_SPAN = "(no program span)"

# XPlane protobuf field numbers (tsl/profiler/protobuf/xplane.proto).
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_NAME, _EVENT_STATS = 2, 5
_STAT_METADATA_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_NAME = 2


# ---------------------------------------------------------------------------
# Protobuf wire format
# ---------------------------------------------------------------------------


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one encoded message; a
    length-delimited value is a zero-copy slice of ``buf``."""

    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _device_tf_ops(plane) -> dict:
    """Instruction text -> ``tf_op`` of one device plane's event metadata."""

    stat_names, raw = {}, []
    for num, value in _fields(plane):
        if num == _PLANE_EVENT_METADATA:
            entry = dict(_fields(value))
            meta = _fields(entry.get(_MAP_VALUE, b""))
            name, stats = None, []
            for n, v in meta:
                if n == _EVENT_NAME:
                    name = _text(v)
                elif n == _EVENT_STATS:
                    stats.append(dict(_fields(v)))
            if name is not None:
                raw.append((name, stats))
        elif num == _PLANE_STAT_METADATA:
            entry = dict(_fields(value))
            meta = dict(_fields(entry.get(_MAP_VALUE, b"")))
            stat_names[entry.get(_MAP_KEY, 0)] = _text(
                meta.get(_STAT_NAME, b""))
    tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
    out = {}
    for name, stats in raw:
        out.setdefault(name, "")
        for st in stats:
            if st.get(_STAT_METADATA_ID) not in tf_op_ids:
                continue
            if _STAT_STR in st:
                out[name] = out[name] or _text(st[_STAT_STR])
            elif _STAT_REF in st:
                out[name] = out[name] or stat_names.get(st[_STAT_REF], "")
    return out


def instruction_scopes(path) -> dict:
    """Name of each device event metadata (for an op, the instruction text
    ``ProfileData`` names its events by) -> its ``tf_op``, "" where it has
    none, over every device plane of the ``.xplane.pb`` at ``path``.  Where
    two programs hold the same text, the first scoped one wins."""

    buf = memoryview(Path(path).read_bytes())
    out = {}
    for num, plane in _fields(buf):
        if num != _SPACE_PLANES:
            continue
        for n, v in _fields(plane):
            if n == _PLANE_NAME:
                if _text(v).startswith(trace_reduce.DEVICE_PREFIX):
                    for text, tf_op in _device_tf_ops(plane).items():
                        out[text] = out.get(text) or tf_op
                break
    return out


# ---------------------------------------------------------------------------
# Scopes, spans and gaps
# ---------------------------------------------------------------------------


def program_scopes(tf_op: str) -> tuple:
    """The program scopes in a ``tf_op`` name stack, outermost first (the
    last component, the primitive, is left out)."""

    stack = tf_op.split(";", 1)[0].rstrip(":").split("/")[:-1]
    return tuple(s for s in stack
                 if s in OPERATORS or s in SUBSCOPES or s.startswith("rule."))


def operator_of(scopes: tuple):
    """The innermost scope that is not a physical sub-scope: the operator
    or stage whose time an instruction counts as."""

    for s in reversed(scopes):
        if s not in SUBSCOPES:
            return s
    return scopes[-1] if scopes else None


@dataclass
class ProgramTrace:
    window_s: float
    busy_s: float                    # mean over devices
    devices: int
    # own device seconds (summed over devices) by program scope path
    by_scopes: dict = field(default_factory=dict)
    unscoped_ops: dict = field(default_factory=dict)  # instruction -> s
    # program span name -> [count, host seconds] inside the window
    spans: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)  # [label, s], longest first
    job_trace_spans: list = field(default_factory=list)  # per job span

    @property
    def scoped(self) -> bool:
        return any(self.by_scopes)

    def operator_s(self, name: str) -> float:
        """Device seconds (mean over devices) an operator or stage took,
        its sub-scopes included."""

        return sum(s for path, s in self.by_scopes.items()
                   if operator_of(path) == name) / self.devices

    def by_operator(self) -> dict:
        out = {}
        for path, s in self.by_scopes.items():
            key = operator_of(path) or NO_SCOPE
            out[key] = out.get(key, 0.0) + s / self.devices
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def unscoped_share(self) -> float:
        total = sum(self.by_scopes.values())
        return self.by_scopes.get((), 0.0) / total if total else 0.0

    @property
    def instrumented(self) -> bool:
        """Whether the program under trace opens spans of its own."""

        return bool(self.spans)


def _program_span(events, mid: float) -> str:
    best, best_len = NO_SPAN, float("inf")
    for ev in events:
        if (ev.start_ns <= mid <= ev.end_ns and ev.duration_ns < best_len
                and ev.name.startswith(SPAN_PREFIXES)):
            best, best_len = ev.name, ev.duration_ns
    return best


def _idle(pd, w0: int, w1: int) -> list:
    """Idle intervals of the first device inside the window."""

    for plane in pd.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        busy = []
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                busy += [(max(ev.start_ns, w0), min(ev.end_ns, w1))
                         for ev in line.events
                         if min(ev.end_ns, w1) > max(ev.start_ns, w0)]
        idle, t = [], w0
        for s, e in trace_reduce._union(busy) + [[w1, w1]]:
            if s > t:
                idle.append((t, s))
            t = max(t, e)
        return idle
    return []


def analyse(path, summary=None, gaps: int = 10) -> ProgramTrace:
    """The program's scopes and spans over the :data:`WINDOW` span of the
    trace at ``path``; ``summary`` is ``trace_reduce.reduce_trace`` of it,
    where the caller has one."""

    pd = trace_reduce.load(str(path))
    if summary is None:
        summary = trace_reduce.reduce_trace(pd, window=WINDOW)
    tf_ops = instruction_scopes(path)
    by_scopes, unscoped = {}, {}
    for text, st in summary.ops.items():
        scopes = program_scopes(tf_ops.get(text, ""))
        by_scopes[scopes] = by_scopes.get(scopes, 0.0) + st.seconds
        if not scopes:
            unscoped[text] = st.seconds
    events, window = trace_reduce._host_events(pd, WINDOW)
    w0, w1 = window.start_ns, window.end_ns
    spans, jobs = {}, []
    for ev in events:
        if not w0 <= ev.start_ns < w1:
            continue
        if ev.name.startswith(SPAN_PREFIXES):
            count_s = spans.setdefault(ev.name, [0, 0.0])
            count_s[0] += 1
            count_s[1] += (min(ev.end_ns, w1) - ev.start_ns) * 1e-9
        elif ev.name == "job":
            jobs.append(ev)
    job_traces = [sum(1 for ev in events if ev.name == "fixpoint.trace"
                      and job.start_ns <= ev.start_ns < job.end_ns)
                  for job in jobs]
    idle = sorted(_idle(pd, w0, w1), key=lambda g: g[0] - g[1])
    labelled = [[_program_span(events, (s + e) / 2), (e - s) * 1e-9]
                for s, e in idle[:gaps]]
    return ProgramTrace(
        window_s=summary.window_s, busy_s=summary.busy_s,
        devices=len(summary.busy_by_device), by_scopes=by_scopes,
        unscoped_ops=unscoped, spans=spans, gaps=labelled,
        job_trace_spans=job_traces)


_CACHE: dict = {}


def read(trace_dir=None, summary=None):
    """:func:`analyse` of the newest trace under ``trace_dir`` (default
    :data:`TRACE_DIR`), parsed once per file per process; None where there
    is no trace, or it lacks a window span or a device plane."""

    try:
        path = trace_reduce.find_xplane(str(trace_dir or TRACE_DIR))
    except FileNotFoundError:
        return None
    key = (path, Path(path).stat().st_mtime_ns, WINDOW)
    if key not in _CACHE:
        try:
            _CACHE[key] = analyse(path, summary)
        except ValueError:
            _CACHE[key] = None
    return _CACHE[key]


# ---------------------------------------------------------------------------
# What the per-layer metrics read
# ---------------------------------------------------------------------------


def device_s_per_iteration(ctx, operator: str):
    """Device seconds per fixpoint iteration under ``operator`` (its
    sub-scopes included); None where the trace holds no program scope."""

    if ctx.trace is None or not ctx.iterations:
        return None
    pt = read(summary=ctx.trace)
    if pt is None or not pt.scoped:
        return None
    return pt.operator_s(operator) / ctx.iterations


def span_s_per_iteration(ctx, name: str):
    """Host seconds per fixpoint iteration in the program's ``name`` spans
    inside the window; None where the program opened no span there."""

    if ctx.trace is None or not ctx.iterations:
        return None
    pt = read(summary=ctx.trace)
    if pt is None or not pt.instrumented:
        return None
    return pt.spans.get(name, [0, 0.0])[1] / ctx.iterations


def summary_json(pt: ProgramTrace, top: int = 15) -> dict:
    scoped = sorted(pt.by_scopes.items(), key=lambda kv: -kv[1])
    return {
        "window_s": pt.window_s, "busy_s": pt.busy_s,
        "unscoped_share": pt.unscoped_share(),
        "by_operator_s": pt.by_operator(),
        "by_scope_s": [["/".join(k) or NO_SCOPE, v / pt.devices]
                       for k, v in scoped[:top]],
        "unscoped_ops_s": [
            [f"{trace_reduce.instruction(t)} ({trace_reduce.op_kind(t)})",
             s / pt.devices]
            for t, s in sorted(pt.unscoped_ops.items(),
                               key=lambda kv: -kv[1])[:top]],
        "spans": pt.spans,
        "fixpoint_trace_per_job": pt.job_trace_spans,
        "idle_gaps": pt.gaps,
    }


if __name__ == "__main__":
    where = Path(sys.argv[1]) if len(sys.argv) > 1 else TRACE_DIR
    found = read(where)
    if found is None:
        sys.exit(f"no trace under {where}")
    print(json.dumps(summary_json(found)))
