"""Device seconds per fixpoint iteration under the executor's ``join``
scope, its sort and pair-expansion sub-scopes included (own time over the
traced window, from the trace's name stacks)."""

from program_trace import device_s_per_iteration


def read(ctx):
    return device_s_per_iteration(ctx, "join")
