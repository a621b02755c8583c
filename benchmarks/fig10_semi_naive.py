"""Fig. 10 (new) — semi-naive (delta-frontier) evaluation microbench.

Measured: one REAL compiled superstep of the dense path vs the
frontier-compacted sparse path at sweeping frontier densities, for the two
Listing-1 workloads (PageRank: sum combine; SSSP: min combine).  The active
mask is pinned to the target density so each row times exactly one
operating point of the adaptive dense<->sparse policy; the acceptance bar
is >= 3x superstep speedup at <= 5% density.

``--sharded`` runs the same sweep on an SPMD mesh over every device (on a
CPU host, 8 virtual devices: it re-execs itself with
``--xla_force_host_platform_device_count=8`` when needed): per-shard compaction, frontier-sized bucket exchanges, and the
collective mode agreement — acceptance bar >= 2x superstep speedup at <= 5%
density over the sharded dense path.

A third workload, weighted SSSP (``sssp_w``: ``Graph.edge_data`` weights
read by the message UDF), sweeps the same densities on the weighted
edge-slab path; its rows are informational — the acceptance bars stay on
the unweighted graph.  A fourth, argmin-SSSP (``sssp_parents``: parent-
pointer payloads through the generic-monoid combine path), pins the cost
of a structured aggregate on the same sweep — also informational.

``--json <path>`` writes the sweep rows as a ``repro-bench-v1`` snapshot
(see :mod:`benchmarks._json`) — the same machine-readable format the CI
``bench-trend`` job and the ``BENCH_*.json`` trajectory files share.
"""

from __future__ import annotations

import os
import subprocess
import sys

# Direct-script invocation (``python benchmarks/fig10_semi_naive.py``) puts
# benchmarks/ on sys.path but not the repo root that holds the package.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import numpy as np
import jax.numpy as jnp

from benchmarks._hw import row, timeit
from repro.core.pregel import Graph, VertexProgram, compile_pregel

DENSITIES = (1.0, 0.5, 0.25, 0.10, 0.05, 0.02, 0.01)


def _graph(N: int, deg: int, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(N, dtype=np.int32), deg)
    dst = rng.integers(0, N, N * deg).astype(np.int32)
    outdeg = np.bincount(src, minlength=N).astype(np.float32)
    return Graph(N, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(outdeg))


def _pagerank(N: int, outdeg) -> VertexProgram:
    od = jnp.asarray(outdeg)
    return VertexProgram(
        init_vertex=lambda ids, vd: jnp.stack(
            [jnp.full((N,), 1.0 / N), od], axis=1),
        message=lambda j, s, ed: s[:, 0] / jnp.maximum(s[:, 1], 1.0),
        apply=lambda j, s, inbox, got: (
            jnp.stack([0.15 / N + 0.85 * inbox, s[:, 1]], axis=1),
            jnp.ones(s.shape[0], jnp.bool_)),
        combine="sum",
    )


def _sssp(N: int) -> VertexProgram:
    inf = jnp.float32(1e9)
    return VertexProgram(
        init_vertex=lambda ids, vd: jnp.where(ids == 0, 0.0, inf),
        message=lambda j, s, ed: s + 1.0,
        apply=lambda j, s, inbox, got: (
            jnp.minimum(s, inbox), jnp.minimum(s, inbox) < s),
        combine="min",
    )


def _weighted_sssp(N: int) -> VertexProgram:
    inf = jnp.float32(1e9)
    return VertexProgram(
        init_vertex=lambda ids, vd: jnp.where(ids == 0, 0.0, inf),
        message=lambda j, s, ed: s + ed,
        apply=lambda j, s, inbox, got: (
            jnp.minimum(s, inbox), jnp.minimum(s, inbox) < s),
        combine="min",
    )


def _weighted(g: Graph) -> Graph:
    w = (((np.arange(g.n_edges) % 7) + 1) * 0.25).astype(np.float32)
    return Graph(g.n_vertices, g.src, g.dst, g.vertex_data,
                 edge_data=jnp.asarray(w))


def _argmin_sssp(N: int) -> VertexProgram:
    """SSSP with parent pointers: the argmin monoid's (dist, parent) rows
    ride the generic XLA combine path — the structured-payload cost pin."""

    inf = jnp.float32(1e9)
    return VertexProgram(
        init_vertex=lambda ids, vd: jnp.stack(
            [jnp.where(ids == 0, 0.0, inf),
             jnp.full(ids.shape, -1.0),
             ids.astype(jnp.float32)], axis=1),
        message=lambda j, s, ed: jnp.stack([s[:, 0] + ed, s[:, 2]], axis=1),
        apply=lambda j, s, inbox, got: (
            jnp.concatenate(
                [jnp.where((inbox[:, 0] < s[:, 0])[:, None],
                           inbox, s[:, :2]), s[:, 2:]], axis=1),
            inbox[:, 0] < s[:, 0]),
        combine="argmin",
    )


def sweep(name, ex, state, emit):
    """Time dense vs sparse supersteps with the frontier pinned per density.

    Uses the executable's own jitted dense superstep, shard-local frontier
    counts, and cap ladder (``sparse_cap_for``) so each row times exactly
    the configuration the adaptive driver would run at that density — on a
    sharded mesh that is the per-shard compacted superstep with the
    capacity negotiated from the maximally-loaded shard."""

    N, E = ex.graph.n_vertices, ex.graph.n_edges
    rng = np.random.default_rng(7)
    dense_fn = ex.jitted_superstep
    speedups = {}
    for rho in DENSITIES:
        n_act = max(1, int(round(rho * N)))
        active = np.zeros(N, bool)
        active[rng.choice(N, n_act, replace=False)] = True
        carry = (state[0], jnp.asarray(active))
        us_dense = timeit(dense_fn, carry, jnp.int32(0))
        counts = ex.shard_edge_counts(carry[1])
        count = int(counts.sum())
        cap = ex.sparse_cap_for(int(counts.max()))
        sparse_fn = ex.sparse_superstep(cap)
        us_sparse = timeit(sparse_fn, carry, jnp.int32(0))
        speedups[rho] = us_dense / us_sparse
        emit(row(
            f"fig10/{name}_rho{rho:g}",
            us_sparse,
            f"measured: sparse cap={cap} ({count}/{E} edges) vs dense "
            f"{us_dense:.0f}us -> {us_dense / us_sparse:.2f}x",
        ))
    return speedups


DESCRIPTION = (
    "Fig. 10: semi-naive (delta-frontier) evaluation — dense vs "
    "frontier-compacted sparse supersteps across frontier densities "
    "(--sharded: the 8-virtual-device SPMD sweep)"
)


def main(emit=print, sharded: bool = False) -> bool:
    """Returns True when every workload clears its acceptance bar at 5%
    density (>= 3x single-shard, >= 2x sharded) — ``--check`` turns a miss
    into a nonzero exit so CI enforces the bar instead of just printing it."""

    N, deg = 16384, 8
    g = _graph(N, deg)
    outdeg = np.asarray(g.vertex_data)

    mesh = None
    tag = ""
    target = 3.0
    if sharded:
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh()
        n_dev = int(np.prod(mesh.devices.shape))
        tag = f"_sharded{n_dev}"
        target = 2.0

    ok = True
    workloads = (
        # (name, program, graph, gates the acceptance bar)
        ("pagerank", _pagerank(N, outdeg), g, True),
        ("sssp", _sssp(N), g, True),
        # Weighted edge-slab path and the generic-monoid (argmin parent-
        # pointer) path: informational rows, no bar — the --check gate
        # stays on the unweighted sum/min workloads.
        ("sssp_w", _weighted_sssp(N), _weighted(g), False),
        ("sssp_parents", _argmin_sssp(N), _weighted(g), False),
    )
    for name, prog, graph, gate in workloads:
        ex = compile_pregel(prog, graph, mesh=mesh, semi_naive=True)
        state = ex.init()
        speedups = sweep(name + tag, ex, state, emit)
        at_5pct = speedups[0.05]
        ok = ok and (at_5pct >= target or not gate)
        emit(row(
            f"fig10/{name}{tag}_speedup_at_5pct", 0.0,
            f"measured: {at_5pct:.2f}x "
            + (f"(target >= {target:g}x) " if gate else "(informational) ")
            + f"threshold={ex.plan.density_threshold:g}",
        ))
    return ok


if __name__ == "__main__":
    from benchmarks._cli import build_parser
    from benchmarks._json import parse_row, write_doc

    parser = build_parser(
        DESCRIPTION,
        check_help="enforce the semi-naive bars: >= 3x sparse superstep "
                   "speedup at <= 5%% density (>= 2x on the sharded sweep)",
    )
    parser.add_argument(
        "--sharded", action="store_true",
        help="run the sweep on an 8-virtual-device SPMD mesh (re-execs "
             "itself with the device-count XLA flag when needed)",
    )
    ns = parser.parse_args()
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if (ns.sharded and jax.default_backend() == "cpu"
            and "xla_force_host_platform_device_count" not in flags):
        # A CPU host needs virtual devices, and the device-count flag must
        # be set before jax initializes: re-exec with the --json operand
        # absolutized so the snapshot still lands in the caller's cwd (the
        # child runs with cwd=_ROOT).  An accelerator host shards over its
        # real devices in this process: a child could not reach them while
        # this process holds them.
        from repro.launch.mesh import virtual_device_env

        argv = ["--sharded"]
        if ns.check:
            argv.append("--check")
        if ns.json is not None:
            argv += ["--json", os.path.abspath(ns.json)]
        env = virtual_device_env(8)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (_ROOT, env.get("PYTHONPATH", "")) if p
        )
        sys.exit(subprocess.call(
            [sys.executable, os.path.abspath(__file__)] + argv,
            env=env, cwd=_ROOT,
        ))
    rows = []

    def emit(line):
        parsed = parse_row(line)
        if parsed is not None:
            rows.append(parsed)
        print(line)

    ok = main(emit=emit, sharded=ns.sharded)
    if ns.json is not None:
        json_path = os.path.abspath(ns.json)
        write_doc(json_path, rows)
        print(f"wrote {len(rows)} rows to {json_path}", file=sys.stderr)
    sys.exit(0 if (ok or not ns.check) else 1)
