"""Device seconds per superstep under the Pregel ``exchange`` scope: the
connector and the combine of both connector calls (inbox and got-message),
with the sorts and segment sums under them (own time over the traced
window, from the trace's name stacks)."""

from program_trace import device_s_per_iteration


def read(ctx):
    return device_s_per_iteration(ctx, "exchange")
