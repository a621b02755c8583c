#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--program 0|1]

For each seed, at the cell's own size: the numbers ``run.py`` compares for
one job of the program, driven through the same entry point as the window
(the lower readings), and the same numbers for the control, the plain
reference computed on the device one precision below the configuration's
(bfloat16 for float32), which has to fail (the upper readings).  Each side
is held to the job's limits as ``run.py`` holds a window job, and its
verdict printed beside its numbers.  One JSON line per seed.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def readings(parts, seed: int, program: bool) -> dict:
    import jax

    job, traffic = parts.job, parts.traffic
    out = {"seed": seed}
    inputs = job.build(parts.cfg, traffic, seed)
    if program:
        handle = job.compile(inputs, traffic)
        value, iters, flags = job.run(handle, traffic)
        value = jax.device_get(value)
        del handle
    host = job.host_inputs(inputs)
    t0 = time.perf_counter()
    ctrl = job.control(inputs, traffic)
    out["control_s"] = time.perf_counter() - t0
    del inputs
    gc.collect()
    t0 = time.perf_counter()
    want = job.reference(host, traffic)
    out["reference_s"] = time.perf_counter() - t0
    if program:
        numbers = job.compare(value, iters, want, traffic)
        numbers.update({k: v for k, v in flags.items() if k in job.LIMITS})
        out["program"] = verdict(numbers, job.LIMITS)
    out["control"] = verdict(
        job.compare(ctrl, traffic["iterations"], want, traffic), job.LIMITS)
    return out


def verdict(numbers: dict, limits: dict) -> dict:
    """``correct`` as ``run.py`` decides it for one job, with each number
    compared beside its limit."""

    checks = run._worst([numbers], limits)
    return {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.enable_cache()
    parts = run.resolve(run.ROOT, run.load_spec(run.ROOT), args.workload)
    run.check_device(parts.cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(parts, seed, bool(args.program))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
