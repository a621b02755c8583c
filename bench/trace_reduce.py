"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``reduce_trace`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
and gives, over the benchmark's ``window`` span:

* busy seconds of each device: the union of the intervals in which an
  operation ran on it (events of the device plane's ``XLA Ops`` line),
  and their mean over the devices;
* device seconds and calls by HLO instruction, keyed by its text as the
  trace names it (result and operand shapes included); seconds are the
  instruction's own time, less that of the ops nested in it (a ``while``
  holds its body's ops);
* the idle gaps of the first device, longest first, each labelled by the
  benchmark span and the innermost host event that cover its middle:
  what the host was doing while the device waited.

Host and device events of one trace share its clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"


@dataclass
class OpStat:
    seconds: float = 0.0     # own time, nested ops excluded
    calls: int = 0


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over devices
    busy_by_device: list
    # the instruction as the trace names it (with its shapes) -> OpStat
    ops: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)  # (label, seconds), longest first

    def top_ops(self, k: int = 10) -> list:
        """[instruction and its op kind, own seconds], most time first."""

        ranked = sorted(self.ops.items(), key=lambda kv: -kv[1].seconds)
        return [[f"{instruction(text)} ({op_kind(text)})", st.seconds]
                for text, st in ranked[:k]]


def instruction(text: str) -> str:
    """``fusion.27`` of ``%fusion.27 = pred[...] fusion(...), ...``."""

    return text.split(" = ", 1)[0].lstrip("%")


def op_kind(text: str) -> str:
    """``fusion``, ``custom-call``, ``while``, ... of an instruction."""

    m = _KIND.search(text.split(" = ", 1)[-1])
    return m.group(1) if m else "?"


_KIND = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _own_times(events, ops: dict) -> None:
    """Add each op's time less that of the ops nested in it (one line's ops
    nest: a ``while`` holds its body's ops) to ``ops``."""

    events.sort(key=lambda ev: (ev[0], -ev[1]))
    stack = []   # [end, OpStat] of the ops open at the current start
    for s, e, text in events:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            stack[-1][1].seconds -= (min(e, stack[-1][0]) - s) * 1e-9
        st = ops.get(text)
        if st is None:
            st = ops[text] = OpStat()
        st.seconds += (e - s) * 1e-9
        st.calls += 1
        stack.append([e, st])


def _host_events(pd, window: str):
    """(thread events, window event) of the host thread holding the span
    named ``window``."""

    host = pd.find_plane_with_name(HOST_PLANE)
    if host is None:
        raise ValueError("trace has no host plane")
    for line in host.lines:
        events = list(line.events)
        for ev in events:
            if ev.name == window:
                return events, ev
    raise ValueError(f"trace has no {window!r} span on any host thread")


def _label(events, spans: set, mid: float) -> str:
    """The innermost benchmark span and the innermost other host event that
    cover ``mid``, as "span > event"."""

    span = inner = None
    span_len = inner_len = float("inf")
    for ev in events:
        if ev.start_ns <= mid <= ev.end_ns:
            if ev.name in spans:
                if ev.duration_ns < span_len:
                    span, span_len = ev.name, ev.duration_ns
            elif ev.duration_ns < inner_len:
                inner, inner_len = ev.name, ev.duration_ns
    if inner is None or inner_len >= span_len:
        return span or "unlabelled"
    return f"{span or 'unlabelled'} > {inner}"


def reduce_trace(pd, window: str = "window", spans=("window", "job"),
                 gaps: int = 10) -> TraceSummary:
    """The summary over the host span named ``window``; idle gaps are
    labelled by the innermost of ``spans`` that covers them."""

    events, window = _host_events(pd, window)
    w0, w1 = window.start_ns, window.end_ns
    busy, ops, first_union = [], {}, None
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            clipped = []
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    clipped.append((s, e, ev.name))
            intervals += [(s, e) for s, e, _ in clipped]
            _own_times(clipped, ops)
        union = _union(intervals)
        busy.append(sum(e - s for s, e in union) * 1e-9)
        if first_union is None:
            first_union = union
    if not busy:
        raise ValueError(f"trace has no device plane ({DEVICE_PREFIX}*)")
    idle, t = [], w0
    for s, e in first_union + [[w1, w1]]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    idle.sort(key=lambda g: g[0] - g[1])
    span_set = set(spans)
    labelled = [[_label(events, span_set, (s + e) / 2), (e - s) * 1e-9]
                for s, e in idle[:gaps]]
    return TraceSummary(window_s=(w1 - w0) * 1e-9,
                        busy_s=sum(busy) / len(busy), busy_by_device=busy,
                        ops=ops, gaps=labelled)
