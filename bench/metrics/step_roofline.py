"""The iteration's share of its HBM roofline, in percent: the least time
the chip could take for one iteration (the job's least HBM bytes of one
iteration, from V and E, over the peak bandwidth) over the device busy
seconds per iteration.  Bandwidth bounds it: an iteration does a few
operations per byte, far below the chip's ratio of FLOP/s to bytes/s."""


def read(ctx):
    if ctx.trace is None or not ctx.iterations or ctx.trace.busy_s <= 0:
        return None
    least_s = ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ctx.trace.busy_s / ctx.iterations)
