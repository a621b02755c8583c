"""Device seconds per fixpoint iteration under the executor's ``groupby``
scope, its sort, run and combine sub-scopes (the ``segment_combine``
kernel among them) included (own time over the traced window, from the
trace's name stacks)."""

from program_trace import device_s_per_iteration


def read(ctx):
    return device_s_per_iteration(ctx, "groupby")
