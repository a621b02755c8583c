"""PageRank through the Pregel front end, as a user calls it.

A job is ``compile_pregel(prog, graph)`` with the planner's connector,
compiled once in set-up, then ``PregelExecutable.run(iterations)`` with its
defaults (the dense plan's on-device loop).  The plain reference is
float64 NumPy over the same edge list; it imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import graph500

# Each number compared, with its limit (PERF.md, "Correctness limits",
# gives the readings each limit was set from).
LIMITS = {
    "rank_max_rel_err": 2e-3,
    "iterations_off": 0,
}


def build(cfg: dict, traffic: dict, seed: int) -> dict:
    """The graph on the device, made from the seed."""

    from repro.core.pregel import Graph

    n, src, dst = graph500.make_edges(cfg, seed)
    outdeg = graph500.out_degree(src, n)
    graph = Graph(n, src, dst, outdeg)
    jax.block_until_ready((src, dst, outdeg))
    return {"n": n, "graph": graph, "V": n, "E": int(src.shape[0])}


def program(n: int, damping: float):
    from repro.core.pregel import VertexProgram

    return VertexProgram(
        init_vertex=lambda ids, outdeg: jnp.stack(
            [jnp.full((n,), 1.0 / n, jnp.float32), outdeg], axis=1),
        message=lambda j, s, ed: s[:, 0] / jnp.maximum(s[:, 1], 1.0),
        apply=lambda j, s, inbox, got: (
            jnp.stack([(1.0 - damping) / n + damping * inbox, s[:, 1]],
                      axis=1),
            jnp.ones(s.shape[0], jnp.bool_)),
        combine="sum",
        name="pagerank",
    )


def compile(inputs: dict, traffic: dict):
    from repro.core.pregel import compile_pregel

    prog = program(inputs["n"], traffic["damping"])
    return compile_pregel(prog, inputs["graph"])


def notes(ex) -> dict:
    return {"connector": ex.plan.connector,
            "plan_notes": ";".join(ex.plan.notes)}


def run(ex, traffic: dict):
    """One job; returns (what to check, iterations, job notes)."""

    res = ex.run(traffic["iterations"])
    ranks = res.state[0][:, 0]
    jax.block_until_ready(ranks)
    return ranks, res.iterations, {"converged": res.converged}


def compiled_texts(ex) -> list:
    """HLO of the executables the jobs ran: the on-device loop is rebuilt
    by each ``run``, so nothing is kept to read."""

    return []


def host_inputs(inputs: dict) -> dict:
    g = inputs["graph"]
    return {"n": inputs["n"], "src": np.asarray(g.src),
            "dst": np.asarray(g.dst)}


def reference(host: dict, traffic: dict) -> np.ndarray:
    """PageRank as the engine defines it, in float64: a vertex that gets no
    message from an active in-neighbour keeps its rank and halts.  ``a``
    counts the edges s -> d, so ``a @ x`` sums ``x`` over in-edges."""

    from scipy import sparse

    n, src, dst = host["n"], host["src"], host["dst"]
    d = traffic["damping"]
    a = sparse.csr_matrix((np.ones(len(src)), (dst, src)), shape=(n, n))
    inv = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)
    rank = np.full(n, 1.0 / n)
    active = np.ones(n, bool)
    for _ in range(traffic["iterations"]):
        inbox = a @ np.where(active, rank * inv, 0.0)
        got = (a @ active.astype(np.float64)) > 0
        rank = np.where(got, (1.0 - d) / n + d * inbox, rank)
        active = got
    return rank


def control(inputs: dict, traffic: dict, dtype=jnp.bfloat16) -> np.ndarray:
    """The reference put in the program's place one precision lower: the
    same recurrence on the device with ranks, messages and sums in
    ``dtype``."""

    g = inputs["graph"]
    n, d = inputs["n"], traffic["damping"]

    @jax.jit
    def go(src, dst, outdeg):
        inv = (1.0 / jnp.maximum(outdeg, 1.0)).astype(dtype)
        rank = jnp.full((n,), 1.0 / n, dtype)
        active = jnp.ones((n,), bool)
        for _ in range(traffic["iterations"]):
            msg = jnp.where(active, rank * inv, 0).astype(dtype)[src]
            inbox = jax.ops.segment_sum(msg, dst, n)
            got = jax.ops.segment_max(active[src].astype(jnp.int32), dst,
                                      n) > 0
            rank = jnp.where(got, ((1.0 - d) / n + d * inbox).astype(dtype),
                             rank)
            active = got
        return rank

    return np.asarray(go(g.src, g.dst, g.vertex_data).astype(jnp.float32),
                      np.float64)


def compare(got: np.ndarray, iterations: int, want: np.ndarray,
            traffic: dict) -> dict:
    """Each number compared for one job."""

    got = np.asarray(got, np.float64)
    rel = np.abs(got - want) / want
    return {"rank_max_rel_err": float(rel.max()),
            "iterations_off": abs(iterations - traffic["iterations"])}


def least_bytes(V: int, E: int) -> int:
    """The fewest HBM bytes one superstep can move, whatever implements it:
    each edge's two int32 ends read once, and each vertex's state (rank and
    degree, float32) and active bit read once and written once."""

    return 8 * E + V * (8 + 8 + 1 + 1)
