"""Device busy seconds per fixpoint iteration over the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.iterations or ctx.trace.busy_s <= 0:
        return None
    return ctx.trace.busy_s / ctx.iterations
