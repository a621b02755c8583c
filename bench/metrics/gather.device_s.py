"""Device seconds per superstep under the Pregel ``gather`` scope: the
source-state and active-bit gathers and the message UDF (own time over the
traced window, from the trace's name stacks)."""

from program_trace import device_s_per_iteration


def read(ctx):
    return device_s_per_iteration(ctx, "gather")
