"""Host seconds per fixpoint iteration in the program's ``fixpoint.trace``
spans inside the window: tracing a new step signature, and lowering and
compiling (or loading) it at its first dispatch.  0 where every step was
memoized; the span count is the program's own recompile counter."""

from program_trace import span_s_per_iteration


def read(ctx):
    return span_s_per_iteration(ctx, "fixpoint.trace")
