#!/usr/bin/env python3
"""Record the small scoped trace that ``test_program_trace.py`` reads.

    python3 bench/tests/record_program_trace.py <out.xplane.pb>

On one TPU: the PPR row-table job at Graph500 scale 12 and the PageRank job
at scale 10, each compiled and warmed up, then one job of each inside a
``window`` span (a ``job`` span around each) under the profiler, as
``bench/run.py --trace 1`` records a window.  The host metadata plane (the
HLO protos, which nothing reads) is left out to keep the file small.
"""

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import program_trace  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

CELLS = (("ppr-rowtable.graph500-20", 12), ("pagerank.graph500-22", 10))
DROP_PLANES = ("/host:metadata",)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def strip(raw: bytes) -> bytes:
    """The XSpace without the planes named in ``DROP_PLANES``."""

    out = bytearray()
    for num, value in program_trace._fields(memoryview(raw)):
        if not isinstance(value, memoryview):
            raise ValueError("an XSpace holds only length-delimited fields")
        if num == 1 and any(
                n == 2 and program_trace._text(v) in DROP_PLANES
                for n, v in program_trace._fields(value)):
            continue
        out += _varint(num << 3 | 2) + _varint(len(value)) + bytes(value)
    return bytes(out)


def main(out: Path) -> None:
    import jax

    run.enable_cache()
    run.check_device(1)
    spec = run.load_spec(run.ROOT)
    jobs = []
    for cell, scale in CELLS:
        parts = run.resolve(run.ROOT, spec, cell)
        cfg = dict(parts.cfg, scale=scale)
        inputs = parts.job.build(cfg, parts.traffic, 7)
        handle = parts.job.compile(inputs, parts.traffic)
        parts.job.run(handle, parts.traffic)
        jobs.append((parts.job, handle, parts.traffic))
    raw_dir = out.parent / (out.name + ".raw")
    shutil.rmtree(raw_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(raw_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for job, handle, traffic in jobs:
            with jax.profiler.TraceAnnotation("job"):
                job.run(handle, traffic)
    jax.profiler.stop_trace()
    out.write_bytes(strip(Path(trace_reduce.find_xplane(
        str(raw_dir))).read_bytes()))
    shutil.rmtree(raw_dir)
    print(json.dumps({"bytes": out.stat().st_size, **program_trace.summary_json(
        program_trace.analyse(out))}))


if __name__ == "__main__":
    main(Path(sys.argv[1]))
