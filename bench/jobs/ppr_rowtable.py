"""Personalized PageRank as Datalog text on a row table, as a user runs it.

A job is the PPR program text (``serving.personalized_pagerank_program``)
compiled once in set-up by ``compile_program`` over the EDB ``edge`` (a
``RowRelation`` of the graph's edge set), ``deg`` and ``seed``, then
``.run(iterations)`` with its defaults (the host driver).  The edge table
is made on the device; ``RowRelation`` takes host rows, so the sorted edge
set is read back once in set-up.  The plain reference is float64 NumPy; it
imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import graph500

LIMITS = {
    "rank_max_rel_err": 1e-3,
    "present_mismatch": 0,
    "iterations_off": 0,
    "storage_fallback": 0,
    "chunked_edb": 0,
}


def seed_vertices(outdeg: np.ndarray, labels: np.ndarray,
                  traffic: dict) -> list:
    """The query's seed vertices: drawn from the traffic's own
    ``source_seed`` among the vertices of the unpermuted draw with
    out-degree at least ``seed_min_out_degree``, then given the run's
    labels, so that one dataset under every seed asks the same query."""

    pool = np.flatnonzero(outdeg[labels] >= traffic["seed_min_out_degree"])
    rng = np.random.default_rng(traffic["source_seed"])
    return [int(labels[v]) for v in
            rng.choice(pool, traffic["seed_vertices"], replace=False)]


def build(cfg: dict, traffic: dict, seed: int) -> dict:
    from repro.core.executor import Relation, RowRelation

    n, src, dst = graph500.make_edges(cfg, seed)
    rows = graph500.unique_rows(src, dst)
    del src, dst
    outdeg = np.bincount(rows[:, 0], minlength=n)
    seeds = seed_vertices(outdeg, graph500.labels(cfg, seed), traffic)
    rels = {
        "edge": RowRelation(n=n, key_positions=(0, 1), rows=rows),
        "deg": Relation.from_columns(n, np.arange(n),
                                     outdeg.astype(np.float32)),
        "seed": Relation.from_columns(n, np.array(seeds),
                                      np.ones(len(seeds), np.float32)),
    }
    return {"n": n, "rows": rows, "outdeg": outdeg, "seeds": seeds,
            "rels": rels, "V": n, "E": int(rows.shape[0])}


def compile(inputs: dict, traffic: dict):
    from repro.core.executor import compile_program
    from repro.core.serving import personalized_pagerank_program

    return compile_program(personalized_pagerank_program(traffic["damping"]),
                           inputs["rels"])


def notes(ex) -> dict:
    return {"plan_notes": ";".join(ex.plan.notes),
            "chunked_edb": ",".join(sorted(ex.chunked_edb)) or "none"}


def run(ex, traffic: dict):
    res = ex.run(traffic["iterations"])
    rank = res.state["rank"]
    out = (rank.present, next(iter(rank.values.values())))
    jax.block_until_ready(out)
    flags = {"storage_fallback": int(res.storage_fallback),
             "chunked_edb": int(bool(ex.chunked_edb))}
    return out, res.iterations, flags


def compiled_texts(ex) -> list:
    return [c.as_text() for c in ex.compiled_steps()]


def host_inputs(inputs: dict) -> dict:
    return {"n": inputs["n"], "rows": inputs["rows"],
            "seeds": inputs["seeds"]}


def reference(host: dict, traffic: dict):
    """(present, rank) of the PPR program after the job's iterations, in
    float64 over the edge set."""

    n, rows, d = host["n"], host["rows"], traffic["damping"]
    src, dst = rows[:, 0], rows[:, 1]
    inv = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)
    seeds = np.array(host["seeds"])
    present = np.zeros(n, bool)
    present[seeds] = True
    rank = np.zeros(n)
    rank[seeds] = 1.0
    for _ in range(traffic["iterations"]):
        msg = np.where(present, d * rank * inv, 0.0)
        r2 = np.bincount(dst, weights=msg[src], minlength=n)
        has2 = np.bincount(dst, weights=present[src], minlength=n) > 0
        seeded = np.zeros(n, bool)
        seeded[seeds] = present[seeds]
        rank = r2 + np.where(seeded, 1.0 - d, 0.0)
        present = has2 | seeded
    return present, rank


def control(inputs: dict, traffic: dict, dtype=jnp.bfloat16):
    """The reference on the device with ranks, messages and sums in
    ``dtype``: the control that has to fail."""

    n, d = inputs["n"], traffic["damping"]
    rows = jnp.asarray(inputs["rows"])
    seeds = jnp.asarray(inputs["seeds"])

    @jax.jit
    def go(src, dst):
        inv = (1.0 / jnp.maximum(
            jax.ops.segment_sum(jnp.ones_like(src, jnp.float32), src, n),
            1.0)).astype(dtype)
        present = jnp.zeros((n,), bool).at[seeds].set(True)
        rank = jnp.zeros((n,), dtype).at[seeds].set(1)
        for _ in range(traffic["iterations"]):
            msg = jnp.where(present, d * rank * inv, 0).astype(dtype)[src]
            r2 = jax.ops.segment_sum(msg, dst, n)
            has2 = jax.ops.segment_max(present[src].astype(jnp.int32), dst,
                                       n) > 0
            seeded = jnp.zeros((n,), bool).at[seeds].set(present[seeds])
            rank = (r2 + jnp.where(seeded, 1.0 - d, 0.0)).astype(dtype)
            present = has2 | seeded
        return present, rank

    present, rank = go(rows[:, 0], rows[:, 1])
    return (np.asarray(present),
            np.asarray(rank.astype(jnp.float32), np.float64))


def compare(got, iterations: int, want, traffic: dict) -> dict:
    got_present, got_rank = (np.asarray(x) for x in got)
    want_present, want_rank = want
    both = got_present & want_present
    rel = (np.abs(np.asarray(got_rank, np.float64)[both] - want_rank[both])
           / want_rank[both])
    return {"rank_max_rel_err": float(rel.max()) if rel.size else 0.0,
            "present_mismatch": int((got_present != want_present).sum()),
            "iterations_off": abs(iterations - traffic["iterations"])}


def least_bytes(V: int, E: int) -> int:
    """The fewest HBM bytes one iteration can move, whatever implements it:
    each edge row's two int32 ids read once, each vertex's degree (float32)
    read once, and its rank (present bit and float32 value) read once and
    written once."""

    return 8 * E + V * (4 + 5 + 5)
