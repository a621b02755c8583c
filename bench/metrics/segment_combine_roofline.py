"""The ``segment_combine`` Pallas kernel's share of its HBM roofline, in
percent: the bytes its calls in the traced window must move (operands read
once, result written once, from the shapes in each call's instruction as
the trace names it) over the peak bandwidth, divided by the device time of
those calls.  Bandwidth bounds it: the kernel folds one add per 8 bytes of
input."""

from hlo_bytes import call_bytes
from trace_reduce import instruction

KERNEL = "segment_combine"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = total_bytes = 0.0
    for text, st in ctx.trace.ops.items():
        if not instruction(text).startswith(KERNEL):
            continue
        seconds += st.seconds
        total_bytes += call_bytes(text) * st.calls
    if seconds <= 0:
        return None
    return 100.0 * total_bytes / ctx.peaks["hbm_bytes_per_s"] / seconds
