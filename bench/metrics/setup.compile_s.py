"""Seconds of set-up spent compiling: the front end and planner (the
benchmark's ``setup.compile`` span around ``compile_pregel`` /
``compile_program``) plus the XLA compiles or persistent-cache loads JAX
reported during the warm-up job.  The warm-up job's own execution is left
out.  Host clock."""


def read(ctx):
    span = ctx.spans.first("setup.compile")
    if span is None:
        return None
    return span[2] - span[1] + ctx.warmup_compile_s
