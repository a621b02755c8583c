#!/usr/bin/env python3
"""Bring-up check: the Datalog engine's main paths on a TPU at a real size.

    python3 chip_smoke.py [--seed N]      # one chip: five phases
    python3 chip_smoke.py --chips 4       # four chips: sharded PageRank only

The graph is a Graph500 Kronecker graph made from the seed with NumPy
(A=0.57, B=C=0.19, scale 22, edge factor 16: 4,194,304 vertices and
67,108,864 edges, about LiveJournal's size and one chip's share of the
paper's web graph).  On one chip vertex labels are not permuted, so
vertex 0 is the hub.  One process runs, through the entry points a user
calls:

* ``pregel-sum``  10 PageRank supersteps via ``compile_pregel`` with the
  planner's connector, then with the ``merging`` connector, whose receiver
  combine is the Pallas ``segment_combine`` kernel; both against NumPy.
* ``pregel-min``  BFS depth from vertex 0 (``combine="min"``, semi-naive,
  adaptive dense/sparse) to convergence, exactly against a NumPy BFS.
* ``generic``     3 iterations of the personalized-PageRank Datalog text
  through ``compile_program`` on a ``RowRelation`` table of the graph's
  edge set, against NumPy.  Iterations, not scale, are cut: one PPR
  iteration of the row-table engine took 54.5 s at scale 22 on a v5e.
* ``serve``       8 requests through ``launch/query_serve``: PPR from 6
  seeds (2 iterations each) and 2 point-reachability probes, each against
  NumPy, over the edge set of a scale-20 graph made from the same seed.
  The cut from scale 22 is forced by the run's 20-minute limit: at scale
  22 each request would run the engine's 54.5 s iterations.
* ``imru``        5 logistic-regression BGD iterations via ``compile_imru``
  on 1,048,576 x 1,024 f32 records (4 GiB: the fig6 shape cut to one
  chip's share, made on the device), against NumPy.

Each phase prints one ``phase=...`` line with its sizes, ``compile_s`` (the
XLA compile seconds JAX reported during the phase), ``run_s`` (the phase's
wall seconds, compiles included), the process's ``peak_bytes_in_use`` so
far, and ``correct=true``.  The merging PageRank superstep and the generic
step must contain ``tpu_custom_call`` in the executables that ran.  The
last line is ``{"ok": true, "device": {...}}``.  The script exits non-zero
without that line when a phase raises or disagrees with its reference,
when JAX finds no TPU, or when the TPU's kind has no hardware spec.

``--chips 4`` runs scale-22 PageRank on ``make_data_mesh(4)`` with each of
the ``dense_psum``, ``merging`` and ``hash_sort`` connectors (3 supersteps
each: four chips are charged four times) and compares each with NumPy and
with the one-chip run on device 0.  Its vertex labels are permuted, as
Graph500 specifies, so that source-owner shards hold even edge shares.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SCALE = 22
EDGE_FACTOR = 16
SERVE_SCALE = 20
PAGERANK_ITERS = 10
FOUR_CHIP_ITERS = 3
PPR_ITERS = 3
SERVE_PPR_ITERS = 2
DAMPING = 0.85
IMRU_RECORDS = 1 << 20
IMRU_FEATURES = 1024
IMRU_ITERS = 5


# ---------------------------------------------------------------------------
# Data and NumPy references
# ---------------------------------------------------------------------------


def kronecker_edges(scale: int, edge_factor: int, seed: int):
    """Graph500 Kronecker edge list (A=0.57, B=C=0.19), int32 src/dst.

    Each level picks one quadrant per edge from one uniform draw: the
    joint law of the reference generator's two draws per level.  Edges
    are made in cache-sized chunks."""

    a, b, c = 0.57, 0.19, 0.19
    m = edge_factor << scale
    rng = np.random.default_rng(seed)
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    for lo in range(0, m, 1 << 16):
        u = rng.random((scale, min(1 << 16, m - lo)), dtype=np.float32)
        s = np.zeros(u.shape[1], np.int32)
        d = np.zeros(u.shape[1], np.int32)
        for bit, ub in enumerate(u):
            s |= (ub >= a + b).view(np.uint8).astype(np.int32) << bit
            d |= (((ub >= a) & (ub < a + b)) | (ub >= a + b + c)).view(
                np.uint8).astype(np.int32) << bit
        src[lo:lo + len(s)] = s
        dst[lo:lo + len(d)] = d
    return src, dst


def unique_edges(src, dst, n: int):
    """The edge set (duplicates dropped), rows in lexicographic order."""

    codes = np.unique(src.astype(np.int64) * n + dst)
    return (codes // n).astype(np.int32), (codes % n).astype(np.int32)


def in_matrix(src, dst, n: int):
    """``A[d, s]`` = the number of edges s -> d, as a SciPy sparse matrix:
    ``A @ x`` sums ``x`` over each vertex's in-edges."""

    from scipy import sparse

    ones = np.ones(len(src), np.float64)
    return sparse.csr_matrix((ones, (dst, src)), shape=(n, n))


def pagerank_ref(a, outdeg, iters: int) -> np.ndarray:
    """The engine's PageRank over ``a = in_matrix(...)``: a vertex with no
    message from an active in-neighbour keeps its rank and halts."""

    n = a.shape[0]
    inv = 1.0 / np.maximum(outdeg, 1)
    rank = np.full(n, 1.0 / n)
    active = np.ones(n, bool)
    for _ in range(iters):
        inbox = a @ np.where(active, rank * inv, 0.0)
        got = (a @ active.astype(np.float64)) > 0
        rank = np.where(got, (1.0 - DAMPING) / n + DAMPING * inbox, rank)
        active = got
    return rank


def bfs_ref(a, roots) -> np.ndarray:
    """Level-synchronous BFS depth over ``a = in_matrix(...)`` (inf where
    unreached)."""

    depth = np.full(a.shape[0], np.inf, np.float32)
    frontier = np.zeros(a.shape[0], bool)
    frontier[roots] = True
    level = 0
    while frontier.any():
        depth[frontier] = level
        level += 1
        frontier = ((a @ frontier.astype(np.float64)) > 0) & np.isinf(depth)
    return depth


def ppr_ref(a, outdeg, seeds, iters: int):
    """The PPR text program's semantics over ``a = in_matrix(...)`` of the
    edge set, one column per seed vertex: (present, rank) after ``iters``."""

    n, k = a.shape[0], len(seeds)
    inv = 1.0 / np.maximum(outdeg, 1)
    cols = np.arange(k)
    present = np.zeros((n, k), bool)
    present[seeds, cols] = True
    rank = np.zeros((n, k))
    rank[seeds, cols] = 1.0
    for _ in range(iters):
        r2 = a @ np.where(present, DAMPING * rank * inv[:, None], 0.0)
        has2 = (a @ present.astype(np.float64)) > 0
        seeded = np.zeros((n, k), bool)
        seeded[seeds, cols] = present[seeds, cols]
        rank = r2 + np.where(seeded, 1.0 - DAMPING, 0.0)
        present = has2 | seeded
    return present, rank


def bgd_ref(x, y, iters: int, lr: float) -> np.ndarray:
    w = np.zeros(x.shape[1], np.float32)
    for _ in range(iters):
        p = 1.0 / (1.0 + np.exp(-(x @ w)))
        w = w - np.float32(lr) * (x.T @ (p - y).astype(np.float32))
    return w


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _report(phase: str, correct: bool, **fields) -> None:
    parts = [f"phase={phase}"] + [f"{k}={v}" for k, v in fields.items()]
    parts += [f"peak_bytes_in_use={_peak_bytes()}",
              f"correct={str(bool(correct)).lower()}"]
    print(" ".join(parts), flush=True)
    if not correct:
        raise AssertionError(f"phase {phase}: result disagrees with NumPy")


_COMPILE_S = [0.0]


def _count_compile(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


def _timed(run):
    """(result, compile_s, run_s) of ``run()``: the XLA compile seconds JAX
    reported while it ran, and its wall seconds, compiles included."""

    import jax

    c0, t0 = _COMPILE_S[0], time.perf_counter()
    res = run()
    jax.block_until_ready(res.state)
    return res, _COMPILE_S[0] - c0, time.perf_counter() - t0


def _has_kernel(compiled) -> bool:
    """Whether any of these executables calls a Pallas TPU kernel."""

    return any("tpu_custom_call" in c.as_text() for c in compiled)


def _pagerank_program(n: int):
    import jax.numpy as jnp
    from repro.core.pregel import VertexProgram

    return VertexProgram(
        init_vertex=lambda ids, outdeg: jnp.stack(
            [jnp.full((n,), 1.0 / n, jnp.float32), outdeg], axis=1),
        message=lambda j, s, ed: s[:, 0] / jnp.maximum(s[:, 1], 1.0),
        apply=lambda j, s, inbox, got: (
            jnp.stack([(1.0 - DAMPING) / n + DAMPING * inbox, s[:, 1]],
                      axis=1),
            jnp.ones(s.shape[0], jnp.bool_)),
        combine="sum",
        name="pagerank",
    )


def _graph(src, dst, n: int):
    import jax.numpy as jnp
    from repro.core.pregel import Graph

    outdeg = np.bincount(src, minlength=n).astype(np.float32)
    return Graph(n, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(outdeg))


def _close(got, want, n: int) -> bool:
    return bool(np.allclose(got, want, rtol=1e-4, atol=1e-3 / n))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_pregel_sum(src, dst, a, n: int):
    from repro.core.pregel import compile_pregel

    want = pagerank_ref(a, np.bincount(src, minlength=n), PAGERANK_ITERS)
    graph = _graph(src, dst, n)
    prog = _pagerank_program(n)
    for connector in (None, "merging"):
        ex = compile_pregel(prog, graph, force_connector=connector)
        res, compile_s, run_s = _timed(
            lambda: ex.run(PAGERANK_ITERS, on_device=False))
        got = np.asarray(res.state[0][:, 0])
        kernel = _has_kernel(ex.jitted_superstep.compiled())
        if connector == "merging" and not kernel:
            raise AssertionError("merging PageRank superstep has no "
                                 "tpu_custom_call: the kernel did not run")
        _report("pregel-sum", _close(got, want, n)
                and res.iterations == PAGERANK_ITERS,
                connector=ex.plan.connector, vertices=n, edges=len(src),
                supersteps=res.iterations, kernel=str(kernel).lower(),
                compile_s=f"{compile_s:.3f}", run_s=f"{run_s:.3f}")


def phase_pregel_min(src, dst, a, n: int):
    import jax.numpy as jnp
    from repro.core.pregel import VertexProgram, compile_pregel

    prog = VertexProgram(
        init_vertex=lambda ids, vd: jnp.where(ids == 0, 0.0, jnp.inf),
        message=lambda j, s, ed: s + 1.0,
        apply=lambda j, s, inbox, got: (jnp.minimum(s, inbox),
                                        jnp.minimum(s, inbox) < s),
        combine="min",
        name="bfs",
    )
    ex = compile_pregel(prog, _graph(src, dst, n), semi_naive=True)
    res, compile_s, run_s = _timed(lambda: ex.run(max_iters=64))
    got = np.asarray(res.state[0])
    want = bfs_ref(a, [0])
    _report("pregel-min", res.converged and np.array_equal(got, want),
            connector=ex.plan.connector, vertices=n, edges=len(src),
            supersteps=res.iterations, reached=int(np.isfinite(want).sum()),
            modes=",".join(res.modes), compile_s=f"{compile_s:.3f}",
            run_s=f"{run_s:.3f}")


def _seed(n: int, v: int):
    from repro.core.executor import Relation

    return Relation.from_columns(n, np.array([v]),
                                 np.array([1.0], np.float32))


def _edge_set(src, dst, n: int):
    """A graph's edge set as EDB relations, with its in-edge matrix and
    out-degrees for the references."""

    from repro.core.executor import Relation, RowRelation

    usrc, udst = unique_edges(src, dst, n)
    outdeg = np.bincount(usrc, minlength=n)
    rels = {
        "edge": RowRelation(n=n, key_positions=(0, 1),
                            rows=np.stack([usrc, udst], axis=1)),
        "deg": Relation.from_columns(n, np.arange(n),
                                     outdeg.astype(np.float32)),
    }
    return in_matrix(usrc, udst, n), outdeg, rels


def _ppr_ok(rank, present, want, n: int) -> bool:
    got_present = np.asarray(rank.present)
    got = np.where(got_present,
                   np.asarray(next(iter(rank.values.values()))), 0.0)
    return np.array_equal(got_present, present) and _close(got, want, n)


def phase_generic(src, dst, n: int):
    from repro.core.executor import compile_program
    from repro.core.serving import personalized_pagerank_program

    a, outdeg, rels = _edge_set(src, dst, n)
    ex = compile_program(personalized_pagerank_program(DAMPING),
                         dict(rels, seed=_seed(n, 0)))
    if ex.chunked_edb:
        raise AssertionError("the planner streams the edge table: "
                             f"{ex.plan.notes}")
    res, compile_s, run_s = _timed(lambda: ex.run(PPR_ITERS))
    kernel = _has_kernel(ex.compiled_steps())
    if not kernel:
        raise AssertionError("generic GroupBy step has no tpu_custom_call: "
                             "the kernel did not run")
    present, want = ppr_ref(a, outdeg, [0], PPR_ITERS)
    ok = (not res.storage_fallback and res.iterations == PPR_ITERS
          and _ppr_ok(res.state["rank"], present[:, 0], want[:, 0], n))
    _report("generic", ok, scale=SCALE, vertices=n, edges=a.nnz,
            iterations=res.iterations,
            cut=f"iterations 10->{PPR_ITERS} (20-minute run limit)",
            kernel=str(kernel).lower(),
            compile_s=f"{compile_s:.3f}", run_s=f"{run_s:.3f}")


def phase_serve(seed: int):
    from repro.core.executor import Relation
    from repro.core.serving import (personalized_pagerank_program,
                                    point_reachability_program)
    from repro.launch.query_serve import (QueryRequest, build_query_server,
                                          serve_request_loop)

    n = 1 << SERVE_SCALE
    a, outdeg, rels = _edge_set(
        *kronecker_edges(SERVE_SCALE, EDGE_FACTOR, seed), n)
    server = build_query_server(rels)
    ppr = personalized_pagerank_program(DAMPING)
    reach = point_reachability_program()
    rng = np.random.default_rng(SERVE_SCALE)
    seeds = [int(v) for v in
             rng.choice(np.flatnonzero(outdeg > 0), 6, replace=False)]
    reached = np.isfinite(bfs_ref(a, [0]))
    indeg = np.diff(a.indptr)
    probes = [int(np.flatnonzero(reached & (indeg > 0))[-1]),
              int(np.flatnonzero(~reached)[0])]
    requests = [QueryRequest(ppr, {"seed": _seed(n, v)},
                             max_iters=SERVE_PPR_ITERS, tag=f"ppr:{i}")
                for i, v in enumerate(seeds)]
    requests += [QueryRequest(
        reach, {"src": Relation.from_columns(n, np.array([0])),
                "dst": Relation.from_columns(n, np.array([p]))},
        max_iters=64, tag=f"reach:{p}") for p in probes]
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    responses = serve_request_loop(server, requests)
    compile_s, wall = _COMPILE_S[0] - c0, time.perf_counter() - t0
    present, want = ppr_ref(a, outdeg, seeds, SERVE_PPR_ITERS)
    ok = len(responses) == len(requests)
    for resp in responses:
        kind, arg = resp.request.tag.split(":")
        if kind == "ppr":
            i = int(arg)
            ok = ok and resp.result.iterations == SERVE_PPR_ITERS and _ppr_ok(
                resp.answers["rank"], present[:, i], want[:, i], n)
        else:
            hit = bool(np.asarray(resp.answers["hit"].present).any())
            ok = (ok and resp.result.converged
                  and hit == bool(reached[int(arg)]))
    _report("serve", ok, scale=SERVE_SCALE, vertices=n, edges=a.nnz,
            cut=f"scale {SCALE}->{SERVE_SCALE} (20-minute run limit)",
            requests=len(responses),
            batched=sum(r.batched for r in responses),
            compile_s=f"{compile_s:.3f}", run_s=f"{wall:.3f}")


def phase_imru(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.core.imru import IMRUTask, compile_imru

    hi = jax.lax.Precision.HIGHEST
    kx, kw = jax.random.split(jax.random.key(seed))
    x_dev = jax.random.normal(kx, (IMRU_RECORDS, IMRU_FEATURES), jnp.float32)
    w_true = jax.random.normal(kw, (IMRU_FEATURES,), jnp.float32)
    y_dev = (jnp.dot(x_dev, w_true, precision=hi) > 0).astype(jnp.float32)
    x, y = np.asarray(x_dev), np.asarray(y_dev)
    lr = 1.0 / IMRU_RECORDS

    def grad(rec, m):
        p = jax.nn.sigmoid(jnp.dot(rec["x"], m, precision=hi))
        return jnp.dot(p - rec["y"], rec["x"], precision=hi)

    task = IMRUTask(init_model=lambda: jnp.zeros((IMRU_FEATURES,),
                                                 jnp.float32),
                    map=grad, update=lambda j, m, g: m - lr * g,
                    name="logreg-bgd")
    ex = compile_imru(task, {"x": x_dev, "y": y_dev})
    res, compile_s, run_s = _timed(
        lambda: ex.run(IMRU_ITERS, on_device=False))
    want = bgd_ref(x, y, IMRU_ITERS, lr)
    got = np.asarray(res.state)
    ok = res.iterations == IMRU_ITERS and bool(np.allclose(
        got, want, rtol=1e-3, atol=1e-3 * float(np.abs(want).max())))
    _report("imru", ok, records=IMRU_RECORDS, features=IMRU_FEATURES,
            bytes=x.nbytes, iterations=res.iterations,
            compile_s=f"{compile_s:.3f}", run_s=f"{run_s:.3f}")


def phase_four_chips(src, dst, n: int, seed: int):
    import jax
    from repro.core.pregel import compile_pregel
    from repro.launch.mesh import make_data_mesh

    # Graph500 permutes the vertex labels.  Unpermuted, source-owner
    # sharding puts 57.7% of the edges on shard 0, and every shard's slab
    # and exchange buckets are padded to that share.
    perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
    src, dst = perm[src], perm[dst]
    share = np.bincount(src // (n // 4), minlength=4).max() / len(src)
    want = pagerank_ref(in_matrix(src, dst, n), np.bincount(src, minlength=n),
                        FOUR_CHIP_ITERS)
    graph = _graph(src, dst, n)
    prog = _pagerank_program(n)
    with jax.default_device(jax.devices()[0]):
        one = np.asarray(compile_pregel(prog, graph).run(
            FOUR_CHIP_ITERS, on_device=False).state[0][:, 0])
    mesh = make_data_mesh(4)
    for connector in ("dense_psum", "merging", "hash_sort"):
        ex = compile_pregel(prog, graph, mesh=mesh, force_connector=connector)
        res, compile_s, run_s = _timed(
            lambda: ex.run(FOUR_CHIP_ITERS, on_device=False))
        devices = {s.device for s in res.state[0].addressable_shards}
        got = np.asarray(res.state[0][:, 0])
        _report("pregel-sum-4chip",
                len(devices) == 4 and _close(got, want, n)
                and _close(got, one, n),
                connector=connector, vertices=n, edges=len(src),
                labels="permuted", max_shard_edge_share=f"{share:.3f}",
                supersteps=res.iterations, shard_devices=len(devices),
                compile_s=f"{compile_s:.3f}", run_s=f"{run_s:.3f}")
        del ex, res
        gc.collect()


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: sharded PageRank on four chips only")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    from repro.core.hardware import hardware_for

    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    hardware_for(dev.device_kind)
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2

    n = 1 << SCALE
    t0 = time.perf_counter()
    src, dst = kronecker_edges(SCALE, EDGE_FACTOR, args.seed)
    print(f"graph: scale={SCALE} vertices={n} edges={len(src)} "
          f"gen_s={time.perf_counter() - t0:.3f}", flush=True)
    if args.chips == 4:
        phase_four_chips(src, dst, n, args.seed)
    else:
        a = in_matrix(src, dst, n)
        phase_pregel_sum(src, dst, a, n)
        gc.collect()
        phase_pregel_min(src, dst, a, n)
        del a
        gc.collect()
        phase_generic(src, dst, n)
        del src, dst
        gc.collect()
        phase_serve(args.seed)
        gc.collect()
        phase_imru(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
