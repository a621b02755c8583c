"""Benchmark runner: one module per paper table/figure + roofline readout.

Prints ``name,us_per_call,derived`` CSV.  ``measured`` rows time real
executions on this host; ``derived`` rows come from the planner/roofline
cost models (CPU container: TPU/2012-cluster numbers cannot be measured).

``--smoke`` runs the fast subset (the fig10 semi-naive superstep sweep plus
the derived-only modules) — the CI-friendly mode that still exercises the
real compiled dense and sparse superstep paths.

``--json <path>`` additionally writes every emitted row as a
``repro-bench-v1`` snapshot (see :mod:`benchmarks._json`) — the format the
CI ``bench-trend`` job diffs against the committed ``BENCH_baseline.json``.

``--help`` lists every benchmark module with its one-line DESCRIPTION (the
same line each module's own ``--help`` leads with), so the whole suite is
self-documenting from here.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import sys
import traceback


def _modules(smoke: bool):
    from benchmarks import (
        fig6_bgd_speedup,
        fig7_bgd_scaleup,
        fig8_pagerank_speedup,
        fig9_connector_plans,
        fig10_semi_naive,
        fig11_generic_engine,
        fig12_fault_tolerance,
        fig13_frontend,
        fig14_storage,
        fig15_serving,
        fig16_outofcore,
        table1_pagerank_scaleup,
        roofline,
        microbench,
    )

    if smoke:
        return (fig10_semi_naive, fig11_generic_engine,
                fig12_fault_tolerance, fig13_frontend, fig14_storage,
                fig15_serving, fig16_outofcore, fig9_connector_plans,
                roofline)
    return (fig6_bgd_speedup, fig7_bgd_scaleup, fig8_pagerank_speedup,
            table1_pagerank_scaleup, fig9_connector_plans,
            fig10_semi_naive, fig11_generic_engine, fig12_fault_tolerance,
            fig13_frontend, fig14_storage, fig15_serving, fig16_outofcore,
            microbench, roofline)


def _build_parser() -> argparse.ArgumentParser:
    lines = []
    for mod in _modules(smoke=False):
        name = mod.__name__.rsplit(".", 1)[-1]
        desc = getattr(mod, "DESCRIPTION", "").split(" — ")[0] \
            or mod.__doc__.splitlines()[0]
        lines.append(f"  {name:<24} {desc}")
    parser = argparse.ArgumentParser(
        description="Run the benchmark suite (one module per paper "
                    "table/figure); prints name,us_per_call,detail CSV.",
        epilog="modules:\n" + "\n".join(lines)
        + "\n\nEach module is also runnable standalone "
          "(python benchmarks/<module>.py --help).",
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "--smoke", action="store_true",
        help="run the fast CI subset instead of the full suite",
    )
    parser.add_argument(
        "--json", metavar="PATH", default=None,
        help="also write every row as a repro-bench-v1 snapshot",
    )
    return parser


def main(argv=None) -> int:
    from benchmarks._json import parse_lines, write_doc
    from repro.launch.compile_cache import enable_compile_cache

    ns = _build_parser().parse_args(argv)
    enable_compile_cache()

    print("name,us_per_call,derived")
    rows = []
    failures = 0
    for mod in _modules(ns.smoke):
        # Capture each module's CSV lines (echoed through) so --json sees
        # every row regardless of how the module emits them.
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf):
                mod.main()
        except Exception:  # noqa: BLE001 - keep the suite running
            failures += 1
            print(f"{mod.__name__},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
        out = buf.getvalue()
        if out:
            sys.stdout.write(out)
        rows.extend(parse_lines(out))
    if ns.json is not None:
        write_doc(ns.json, rows)
        print(f"wrote {len(rows)} rows to {ns.json}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
