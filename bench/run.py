#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the accelerator it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``, the graph's generator and
sizes) and its traffic (``bench/traffic/<traffic>.json``, the job and its
parameters).  The traffic names a job kind (``bench/jobs/<job>.py``: the
user entry point it drives, its plain reference, the numbers compared and
their limits, and the least HBM bytes of one iteration).  Each per-layer
metric is a reader of its own (``bench/metrics/<metric>.py``).

A run: make the inputs on the device from ``--seed``, compile the job
through the program's entry point, warm it up with one whole job (all of
that is set-up), then run whole jobs back to back until the first one that
ends after ``--seconds`` (the window).  With ``--trace 1`` the window runs
under the profiler and the per-layer metrics are read from the trace and
the benchmark's own spans.  Once the window has closed and the program's
state is freed, the plain reference checks every job of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (jobs), ``metrics``, ``device``, with
``--trace 1`` a ``breakdown``, and last the numbers compared beside their
limits (``checks``), which also end standard error.  The run fails, and
prints no result, where JAX finds no TPU or fewer chips than the cell asks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding a cell's parts by name
# ---------------------------------------------------------------------------


def load_spec(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _find(root: Path, kind: str, name: str, suffix: str) -> Path:
    for base in dict.fromkeys((root / "bench", BENCH_DIR)):
        path = base / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind}/{name}{suffix} under {root / 'bench'}"
                            f" or {BENCH_DIR}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(root: Path, spec: dict, workload: str) -> SimpleNamespace:
    """The cell's entry, configuration, traffic, job module and per-layer
    metric readers, found by the names in ``BENCHMARK.json``."""

    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        _find(root, "traffic", cell["traffic"], ".json").read_text())
    job = load_module(_find(root, "jobs", traffic["job"], ".py"))
    readers = {
        m["name"]: load_module(_find(root, "metrics", m["name"], ".py"))
        for m in spec["per_layer"]
        if workload in m.get("workloads", [workload])
    }
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    units.update({m["name"]: m["unit"] for m in spec["end_to_end"]})
    return SimpleNamespace(cell=cell, cfg=cfg, traffic=traffic, job=job,
                           readers=readers, e2e=e2e, units=units)


# ---------------------------------------------------------------------------
# Device, spans and counters
# ---------------------------------------------------------------------------


def check_device(chips: int) -> dict:
    """The peaks of the TPU this process holds; raises :class:`NoChip`
    where JAX finds no TPU or fewer than ``chips`` of them, and ValueError
    where the TPU's kind has no published peaks."""

    import jax

    from peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked, {len(devices)} found")
    return peaks_for(devices[0].device_kind)


class Spans:
    """The benchmark's host spans: kept on the host clock, and written into
    the profiler's trace when one is being recorded."""

    def __init__(self):
        self.done = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.done.append((name, t0, time.perf_counter()))

    def first(self, name: str):
        return next((s for s in self.done if s[0] == name), None)


class Counters:
    """XLA programs obtained (compiled or read from the persistent cache),
    the seconds that took, and persistent-cache hits, as JAX reports them."""

    def __init__(self):
        import jax

        self.programs = self.hits = 0
        self.compile_s = 0.0

        def on_duration(event, secs, **_):
            if event == COMPILE_EVENT:
                self.programs += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.programs, self.hits


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def _note(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _worst(per_job: list, limits: dict) -> dict:
    worst = {}
    for numbers in per_job:
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, v), v)
    return {k: {"value": worst[k], "limit": limits[k]} for k in limits
            if k in worst}


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed directory
    inside the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), with
    every program in it, so that a cell's second run compiles nothing."""

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def run_cell(args, root: Path = ROOT, device_check=check_device) -> dict:
    import jax

    parts = resolve(root, load_spec(root), args.workload)
    peaks = device_check(parts.cell["chips"])
    devices = jax.devices()[:parts.cell["chips"]]
    job, traffic = parts.job, parts.traffic
    spans, counters = Spans(), Counters()
    _note(workload=args.workload, seed=args.seed, platform=devices[0].platform,
          kind=devices[0].device_kind, devices=len(jax.devices()),
          compile_cache=jax.config.jax_compilation_cache_dir)

    with spans("setup.inputs"):
        inputs = job.build(parts.cfg, traffic, args.seed)
    with spans("setup.compile"):
        handle = job.compile(inputs, traffic)
    compile_s0 = counters.compile_s
    with spans("setup.warmup"):
        job.run(handle, traffic)
    warmup_compile_s = counters.compile_s - compile_s0
    V, E = inputs["V"], inputs["E"]
    programs, hits = counters.snapshot()
    _note(vertices=V, edges=E, setup_compiles=programs - hits,
          setup_cache_loads=hits,
          **{k: v for k, v in job.notes(handle).items()})

    trace_dir = root / ".bench_trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    programs0, hits0 = counters.snapshot()
    results, errors = [], []
    t_open = time.perf_counter()
    with spans("window"):
        while True:
            try:
                with spans("job"):
                    results.append(job.run(handle, traffic))
            except Exception:  # a job that raises is a failed job
                errors.append(traceback.format_exc(limit=4))
                break
            if time.perf_counter() - t_open >= args.seconds:
                break
    window_s = time.perf_counter() - t_open
    programs1, hits1 = counters.snapshot()
    if args.trace:
        jax.profiler.stop_trace()
    memory_peak = peak_bytes(devices)
    iterations = sum(r[1] for r in results)
    texts = job.compiled_texts(handle)
    _note(jobs=len(results) + len(errors), iterations=iterations,
          window_s=window_s, programs_in_window=programs1 - programs0,
          compiles_in_window=(programs1 - programs0) - (hits1 - hits0),
          cache_loads_in_window=hits1 - hits0,
          tpu_custom_call=str(any("tpu_custom_call" in t for t in texts))
          .lower() if texts else "not_read",
          peak_bytes_in_use=memory_peak)
    for r in results[:1]:
        _note(**{f"job_{k}": v for k, v in r[2].items()})

    with spans("readback"):
        got = [(jax.device_get(r[0]), r[1], r[2]) for r in results]
        host = job.host_inputs(inputs)
    del results, handle, inputs
    gc.collect()
    with spans("reference"):
        want = job.reference(host, traffic)
    _, t0, t1 = spans.first("reference")
    _note(reference_s=t1 - t0)
    per_job, failed = [], len(errors)
    for value, iters, flags in got:
        numbers = job.compare(value, iters, want, traffic)
        numbers.update({k: v for k, v in flags.items() if k in job.LIMITS})
        per_job.append(numbers)
        failed += any(numbers[k] > job.LIMITS[k] for k in job.LIMITS)
    checks = _worst(per_job, job.LIMITS)
    attempted = len(got) + len(errors)
    correct = failed == 0 and attempted > 0
    for e in errors:
        print(e, file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        import trace_reduce

        trace = trace_reduce.reduce_trace(
            trace_reduce.load(trace_reduce.find_xplane(str(trace_dir))))
        ctx = SimpleNamespace(spans=spans, trace=trace, iterations=iterations,
                              least_bytes=job.least_bytes(V, E), peaks=peaks,
                              warmup_compile_s=warmup_compile_s)
        metrics = {}
        for name, reader in parts.readers.items():
            value = reader.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": parts.units[name]}
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        out.update(metrics=metrics, device=device,
                   breakdown={"device_ops": trace.top_ops(10),
                              "idle_gaps": trace.gaps[:10]})
    else:
        values = {"iteration_s": window_s / max(iterations, 1),
                  "setup_s": setup_s}
        out.update(metrics={m["name"]: {"value": values[m["name"]],
                                        "unit": m["unit"]}
                            for m in parts.e2e},
                   device=device)
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    enable_cache()
    try:
        out = run_cell(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
