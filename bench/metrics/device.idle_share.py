"""Share of the window in which no operation ran on the device, in
percent: 100 x (1 - busy / window), busy being the union of the device's
op intervals in the trace (mean over chips)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
