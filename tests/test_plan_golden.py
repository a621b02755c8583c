"""Golden ``plan.notes`` snapshots for the cost-based planner.

The applied-rule notes are the planner's public record of which rewrites
fired (tests, EXPERIMENTS.md and the benchmarks all key off them), so a
planner change that silently adds, drops, or reorders a rewrite decision on
any mesh shape must show up as a diff here.  Snapshots cover 1/2/4-way data
meshes plus a 2x2 pod mesh for both ``plan_pregel`` and ``plan_imru``,
unweighted and weighted (``edge_attr_bytes > 0``) graph statistics.
"""

import dataclasses

import numpy as np

from repro.core.hardware import MeshSpec
from repro.core.planner import IMRUStats, PregelStats, plan_imru, plan_pregel

MESHES = {
    "1way": MeshSpec((("data", 1),)),
    "2way": MeshSpec((("data", 2),)),
    "4way": MeshSpec((("data", 4),)),
    "2x2pod": MeshSpec((("pod", 2), ("data", 2))),
}

PREGEL_STATS = PregelStats(n_vertices=4096, n_edges=65536,
                           vertex_bytes=4, msg_bytes=4)
IMRU_STATS = IMRUStats(n_records=1_000_000, record_bytes=400,
                       model_bytes=16 * 2**20, stat_bytes=16 * 2**20,
                       flops_per_record=1e4)

_PREGEL_BASE = (
    "storage-selection(dense-indexed-state)",
    "join-algorithm(index-gather)",
    "loop-invariant-caching(graph)",
    "early-grouping(sender-combine)",
    "connector(dense_psum)",
)

PREGEL_GOLDEN = {
    # Single shard: no interconnect, the sparse path wins below 50% density.
    ("1way", True): _PREGEL_BASE + (
        "semi-naive(adaptive dense<->sparse @ density 0.5)",
    ),
    # Sharded: the per-shard compaction + frontier-sized bucket-a2a plan is
    # recorded, but on this tiny (65K-edge) graph the alpha terms of the
    # sparse exchange never beat one dense psum on the TPU hardware model —
    # the threshold solves to the "sparse never wins" sentinel.
    ("2way", True): _PREGEL_BASE + (
        "sharded-delta(per-shard compaction, bucket-a2a x2, "
        "collective mode-agreement)",
        "semi-naive(adaptive dense<->sparse @ density 0)",
    ),
    ("4way", True): _PREGEL_BASE + (
        "sharded-delta(per-shard compaction, bucket-a2a x4, "
        "collective mode-agreement)",
        "semi-naive(adaptive dense<->sparse @ density 0)",
    ),
    ("2x2pod", True): _PREGEL_BASE + (
        "sharded-delta(per-shard compaction, bucket-a2a x4, "
        "collective mode-agreement)",
        "semi-naive(adaptive dense<->sparse @ density 0)",
    ),
    ("1way", False): _PREGEL_BASE,
    ("2way", False): _PREGEL_BASE,
    ("4way", False): _PREGEL_BASE,
    ("2x2pod", False): _PREGEL_BASE,
}

_IMRU_BASE = (
    "loop-invariant-caching(training_data)",
    "early-aggregation(map-local)",
    "model-volume(replicate-params)",
)

IMRU_GOLDEN = {
    "1way": _IMRU_BASE + ("aggregation-tree(flat)",),
    "2way": _IMRU_BASE + ("aggregation-tree(flat)",),
    "4way": _IMRU_BASE + ("aggregation-tree(flat)",),
    # Multi-pod: the 16 MB gradient crosses DCN — ZeRO-1 reduce-scatter wins.
    "2x2pod": _IMRU_BASE + ("aggregation-tree(scatter)",),
}


WEIGHTED_STATS = dataclasses.replace(PREGEL_STATS, edge_attr_bytes=4)

# Weighted graphs: the edge-payload note lands right after the connector
# choice, before the semi-naive policy notes.
PREGEL_WEIGHTED_GOLDEN = {
    ("1way", True): _PREGEL_BASE + (
        "edge-payload(4B/edge)",
        "semi-naive(adaptive dense<->sparse @ density 0.5)",
    ),
    ("4way", True): _PREGEL_BASE + (
        "edge-payload(4B/edge)",
        "sharded-delta(per-shard compaction, bucket-a2a x4, "
        "collective mode-agreement)",
        "semi-naive(adaptive dense<->sparse @ density 0)",
    ),
    ("1way", False): _PREGEL_BASE + ("edge-payload(4B/edge)",),
    ("4way", False): _PREGEL_BASE + ("edge-payload(4B/edge)",),
}


def test_pregel_plan_notes_golden():
    for (mesh_name, semi_naive), want in PREGEL_GOLDEN.items():
        plan = plan_pregel(PREGEL_STATS, MESHES[mesh_name],
                           semi_naive=semi_naive)
        assert plan.notes == want, (mesh_name, semi_naive, plan.notes)


def test_pregel_weighted_plan_notes_golden():
    for (mesh_name, semi_naive), want in PREGEL_WEIGHTED_GOLDEN.items():
        plan = plan_pregel(WEIGHTED_STATS, MESHES[mesh_name],
                           semi_naive=semi_naive)
        assert plan.notes == want, (mesh_name, semi_naive, plan.notes)


def test_imru_plan_notes_golden():
    for mesh_name, want in IMRU_GOLDEN.items():
        plan = plan_imru(IMRU_STATS, MESHES[mesh_name])
        assert plan.notes == want, (mesh_name, plan.notes)


def test_pregel_sharded_threshold_nonzero_at_scale():
    """On a production-sized graph the frontier-sized bucket exchange DOES
    beat the dense psum below a density threshold that shrinks as the dense
    exchange amortizes over more shards — pin the ladder's solutions so the
    cost model can't drift silently."""

    stats = PregelStats(n_vertices=10_000_000, n_edges=500_000_000,
                        vertex_bytes=8, msg_bytes=8)
    thresholds = {
        dp: plan_pregel(stats, MeshSpec((("data", dp),)),
                        semi_naive=True).density_threshold
        for dp in (2, 8, 16)
    }
    assert thresholds == {2: 0.0625, 8: 0.0078125, 16: 0.00390625}


def test_pregel_weighted_payload_shifts_threshold_ladder():
    """Per-edge attribute bytes widen the edge pipeline the dense path pays
    at full E, so compaction wins earlier: the weighted ladder crosses at a
    density >= the unweighted one, strictly higher where the power-of-two
    ladder resolves the difference (dp=8 for the reference stats)."""

    stats = PregelStats(n_vertices=10_000_000, n_edges=500_000_000,
                        vertex_bytes=8, msg_bytes=8, edge_attr_bytes=8)
    thresholds = {
        dp: plan_pregel(stats, MeshSpec((("data", dp),)),
                        semi_naive=True).density_threshold
        for dp in (2, 8, 16)
    }
    assert thresholds == {2: 0.0625, 8: 0.015625, 16: 0.00390625}


MONOID_STATS = dataclasses.replace(PREGEL_STATS, msg_bytes=8,
                                   combine="argmin")

# Generic-monoid aggregates (no psum-scatter fast path): the dense
# connector pays an all-gather, which flips the sharded choice to the
# sparse hash_sort plan; the monoid's payload-width term is recorded right
# after the connector note.
PREGEL_MONOID_GOLDEN = {
    ("1way", False): _PREGEL_BASE + (
        "combine-monoid(argmin, 8B/msg, xla-generic)",
    ),
    ("1way", True): _PREGEL_BASE + (
        "combine-monoid(argmin, 8B/msg, xla-generic)",
        "semi-naive(adaptive dense<->sparse @ density 0.5)",
    ),
    ("4way", False): _PREGEL_BASE[:-1] + (
        "connector(hash_sort)",
        "combine-monoid(argmin, 8B/msg, xla-generic)",
    ),
    ("4way", True): _PREGEL_BASE[:-1] + (
        "connector(hash_sort)",
        "combine-monoid(argmin, 8B/msg, xla-generic)",
        "sharded-delta(per-shard compaction, bucket-a2a x4, "
        "collective mode-agreement)",
        "semi-naive(adaptive dense<->sparse @ density 0)",
    ),
}


def test_pregel_monoid_plan_notes_golden():
    for (mesh_name, semi_naive), want in PREGEL_MONOID_GOLDEN.items():
        plan = plan_pregel(MONOID_STATS, MESHES[mesh_name],
                           semi_naive=semi_naive)
        assert plan.notes == want, (mesh_name, semi_naive, plan.notes)


def test_pregel_fast_path_monoid_keeps_psum_connector():
    # mean rides the sum fast path: no all-gather penalty, dense_psum
    # stays the 4-way winner, and the note records the ridden path.
    stats = dataclasses.replace(PREGEL_STATS, msg_bytes=8, combine="mean")
    plan = plan_pregel(stats, MESHES["4way"])
    assert plan.connector == "dense_psum"
    assert "combine-monoid(mean, 8B/msg, sum-fast-path)" in plan.notes


def test_pregel_sparse_cap_floor_scales_down_for_small_shards():
    """The planner-derived per-shard compaction capacity: capped at 64 for
    production slabs, but no more than a quarter of a small local slab so
    the sparse path can engage on test-sized graphs."""

    big = plan_pregel(PREGEL_STATS, MESHES["4way"], semi_naive=True)
    assert big.sparse_cap_floor == 64
    small = plan_pregel(
        PregelStats(n_vertices=64, n_edges=288, vertex_bytes=4, msg_bytes=4),
        MeshSpec((("data", 8),)), semi_naive=True,
    )
    assert small.sparse_cap_floor == 8
    assert small.sparse_cap_for(3) == 8
    assert small.sparse_cap_for(100) == 128


# ---------------------------------------------------------------------------
# Generic-program plans (the unified executor)
# ---------------------------------------------------------------------------
#
# The listing snapshots above double as the refactor guard: compile_pregel /
# compile_imru now lower through repro.core.executor, and their plan.notes
# must stay byte-identical (tests/test_executor.py additionally asserts the
# compile_program-dispatched plans carry the same notes).  The snapshots
# below pin the NEW generic-program plans: dense-grid storage, fixpoint
# phases, and the per-GroupBy Fig.-9 connector selection.

GENERIC_N = 64

GENERIC_GOLDEN = {
    ("transitive-closure", False): (
        "storage-selection(dense-grid[n=64])",
        "loop-invariant-caching(edb-grids)",
    ),
    ("connected-components", False): (
        "storage-selection(dense-grid[n=64])",
        "loop-invariant-caching(edb-grids)",
        "groupby(C2: min via dense-reduce, 4096 rows -> 64)",
    ),
    ("connected-components", True): (
        "storage-selection(dense-grid[n=64])",
        "loop-invariant-caching(edb-grids)",
        "groupby(C2: min via dense-reduce, 4096 rows -> 64)",
        "semi-naive(C2: cc -> Δcc)",
    ),
    ("same-generation", False): (
        "storage-selection(dense-grid[n=64])",
        "loop-invariant-caching(edb-grids)",
    ),
    ("pagerank-threshold", False): (
        "storage-selection(dense-grid[n=64])",
        "loop-invariant-caching(edb-grids)",
        "fixpoint-phases(rank -> reach)",
        "groupby(P2: sum via dense-reduce, 4096 rows -> 64)",
    ),
}

GENERIC_STRUCTURE = {
    # Operator skeletons of the recursive rules — the logical plan is the
    # execution contract now, so its shape is pinned alongside the notes.
    "transitive-closure": {
        "T2": ("T2", "tc", ("Project", ("Join", ("ScanState",), ("ScanEDB",)))),
    },
    "connected-components": {
        "C2": ("C2", "cc", ("GroupBy", ("Join", ("ScanState",), ("ScanEDB",)))),
    },
    "pagerank-threshold": {
        "P4": ("P4", "rankF", ("Frontier",)),
        "H2": ("H2", "reach",
               ("Project",
                ("Join",
                 ("Join", ("ScanState",), ("ScanEDB",)),
                 ("ScanState",)))),
    },
}


def _generic_executables():
    import numpy as np

    from repro.core.executor import Relation, compile_program
    from repro.core.listings import (
        connected_components_program,
        pagerank_threshold_program,
        same_generation_program,
        transitive_closure_program,
    )

    n = GENERIC_N
    rng = np.random.default_rng(0)
    src, dst = rng.integers(0, n, 96), rng.integers(0, n, 96)
    edge = Relation.from_columns(n, src, dst)
    node2 = Relation.from_columns(
        n, np.arange(n), np.arange(n, dtype=np.float32)
    )
    deg = np.bincount(src, minlength=n).astype(np.float32)
    node4 = Relation.from_columns(
        n, np.arange(n), np.full(n, 1.0 / n, np.float32), deg,
        np.full(n, 0.15 / n, np.float32),
    )
    out = {}
    for (name, semi_naive), prog, rels in (
        (("transitive-closure", False), transitive_closure_program(),
         {"edge": edge}),
        (("connected-components", False), connected_components_program(),
         {"edge": edge, "node": node2}),
        (("connected-components", True), connected_components_program(),
         {"edge": edge, "node": node2}),
        (("same-generation", False), same_generation_program(),
         {"parent": edge}),
        (("pagerank-threshold", False), pagerank_threshold_program(),
         {"edge": edge, "node": node4}),
    ):
        out[(name, semi_naive)] = compile_program(
            prog, rels, semi_naive=semi_naive
        )
    return out


def test_generic_program_plan_notes_golden():
    for key, ex in _generic_executables().items():
        assert ex.plan.notes == GENERIC_GOLDEN[key], (key, ex.plan.notes)


def test_generic_program_logical_structure_golden():
    for key, ex in _generic_executables().items():
        name, semi_naive = key
        want = GENERIC_STRUCTURE.get(name)
        if want is None or semi_naive:
            continue
        got = {df.label: df.structure() for df in ex.logical.body}
        for label, structure in want.items():
            assert got[label] == structure, (name, label, got[label])


def test_high_domain_tc_storage_selection_golden():
    # A 65536-vertex TC over sparse RowRelation edges: the cost model must
    # pick row tables for both predicates (the dense n^2 grid is 2^32
    # cells) and the note pins the chosen slab capacities — the EDB cap
    # from the real 57344-row count, the recursive cap at the slab ceiling.
    from repro.core.executor import RowRelation, compile_program
    from repro.core.listings import transitive_closure_program

    n, block = 65536, 8
    src = np.concatenate(
        [np.arange(s, s + block - 1) for s in range(0, n, block)])
    ex = compile_program(
        transitive_closure_program(),
        {"edge": RowRelation.from_columns(n, src, src + 1)},
    )
    assert ex.storage == {"edge": "row-table", "tc": "row-table"}
    assert ex.plan.notes == (
        "storage-selection(n=65536, edge=row-table[cap=524288], "
        "tc=row-table[cap=1048576])",
        "loop-invariant-caching(edb-grids)",
    )


# ---------------------------------------------------------------------------
# Parsed-text programs + the rewrite pass
# ---------------------------------------------------------------------------
#
# The text frontend's plans are pinned twice: rewrite-off must reproduce
# the hand-built GENERIC_GOLDEN notes byte-for-byte (the frontend adds no
# planning surface of its own), and rewrite-on must append exactly one
# rewrite(...) entry recording which of the three rewrites fired.  Each
# rewrite demonstrably fires on at least one program: join-reorder on
# TC/CC/pagerank, select-pushdown on negated-reach, CSE on same-generation.

GENERIC_REWRITE_GOLDEN = {
    ("transitive-closure", False): GENERIC_GOLDEN[
        ("transitive-closure", False)] + (
        "rewrite(join-reorder: T2, pushdown: none, cse: 0 shared)",
    ),
    ("connected-components", False): GENERIC_GOLDEN[
        ("connected-components", False)] + (
        "rewrite(join-reorder: C2, pushdown: none, cse: 0 shared)",
    ),
    # The rewrite entry lands after the semi-naive entries: with the
    # iterated program-cardinality estimates, Δcc reads ~1/8 of cc's real
    # ~64-row count — smaller than the 96-row edge relation, so the
    # source order (delta first) is already optimal and no reorder fires.
    ("connected-components", True): GENERIC_GOLDEN[
        ("connected-components", True)] + (
        "rewrite(join-reorder: none, pushdown: none, cse: 0 shared)",
    ),
    ("same-generation", False): GENERIC_GOLDEN[
        ("same-generation", False)] + (
        "rewrite(join-reorder: none, pushdown: none, cse: 1 shared)",
    ),
    ("pagerank-threshold", False): GENERIC_GOLDEN[
        ("pagerank-threshold", False)] + (
        "rewrite(join-reorder: P2+P3, pushdown: none, cse: 0 shared)",
    ),
    ("negated-reach", False): (
        "storage-selection(dense-grid[n=64])",
        "loop-invariant-caching(edb-grids)",
        "rewrite(join-reorder: none, pushdown: 1 select, cse: 0 shared)",
    ),
}

GENERIC_REWRITE_STRUCTURE = {
    # Join-reorder flips T2 to scan the 96-row edge relation before the
    # 4096-cell recursive state grid.
    "transitive-closure": {
        "T2": ("T2", "tc", ("Project", ("Join", ("ScanEDB",), ("ScanState",)))),
    },
    # Select-pushdown sinks the W < 3 guard below the AntiJoin into its
    # positive side; the negated blocked(Y) scan is untouched.
    "negated-reach": {
        "N2": ("N2", "reach",
               ("Project",
                ("AntiJoin",
                 ("Join",
                  ("Join", ("ScanState",), ("ScanEDB",)),
                  ("Select", ("ScanEDB",))),
                 ("ScanEDB",)))),
    },
}


def _parsed_executables(rewrite):
    import numpy as np

    from repro.core.executor import Relation, compile_program
    from repro.core.listings import (
        parsed_connected_components_program,
        parsed_negated_reach_program,
        parsed_pagerank_threshold_program,
        parsed_same_generation_program,
        parsed_transitive_closure_program,
    )

    n = GENERIC_N
    rng = np.random.default_rng(0)
    src, dst = rng.integers(0, n, 96), rng.integers(0, n, 96)
    edge = Relation.from_columns(n, src, dst)
    node2 = Relation.from_columns(
        n, np.arange(n), np.arange(n, dtype=np.float32)
    )
    deg = np.bincount(src, minlength=n).astype(np.float32)
    node4 = Relation.from_columns(
        n, np.arange(n), np.full(n, 1.0 / n, np.float32), deg,
        np.full(n, 0.15 / n, np.float32),
    )
    source = Relation.from_columns(
        n, np.arange(8),
        np.array([1, 0, 1, 1, 0, 1, 0, 1], np.float32),
    )
    blocked = Relation.from_columns(n, np.array([3, 9, 27]))
    nodew = Relation.from_columns(
        n, np.arange(n), (np.arange(n) % 5).astype(np.float32)
    )
    out = {}
    for (name, semi_naive), prog, rels in (
        (("transitive-closure", False),
         parsed_transitive_closure_program(), {"edge": edge}),
        (("connected-components", False),
         parsed_connected_components_program(),
         {"edge": edge, "node": node2}),
        (("connected-components", True),
         parsed_connected_components_program(),
         {"edge": edge, "node": node2}),
        (("same-generation", False),
         parsed_same_generation_program(), {"parent": edge}),
        (("pagerank-threshold", False),
         parsed_pagerank_threshold_program(),
         {"edge": edge, "node": node4}),
        (("negated-reach", False),
         parsed_negated_reach_program(),
         {"source": source, "edge": edge, "node": nodew,
          "blocked": blocked}),
    ):
        out[(name, semi_naive)] = compile_program(
            prog, rels, semi_naive=semi_naive, rewrite=rewrite
        )
    return out


def test_parsed_program_rewrite_off_matches_hand_built_notes():
    """PR 5's hand-built golden notes ARE the parsed programs' notes when
    the rewrite pass is off — the frontend adds zero planning surface."""

    for key, ex in _parsed_executables(rewrite=False).items():
        if key in GENERIC_GOLDEN:
            assert ex.plan.notes == GENERIC_GOLDEN[key], (key, ex.plan.notes)
        else:  # negated-reach is new in this PR; pin it directly.
            assert ex.plan.notes == GENERIC_REWRITE_GOLDEN[key][:-1], (
                key, ex.plan.notes)


def test_parsed_program_rewrite_on_notes_golden():
    for key, ex in _parsed_executables(rewrite=True).items():
        assert ex.plan.notes == GENERIC_REWRITE_GOLDEN[key], (
            key, ex.plan.notes)


# ---------------------------------------------------------------------------
# Explicit sharded exchanges + out-of-core chunking (PR 10)
# ---------------------------------------------------------------------------
#
# The exchange(...) / chunking(...) notes are the planner's public record of
# the PR-10 physical decisions — which Join/GroupBy sites leave GSPMD for the
# explicit key-hash bucket all-to-all (and its per-shard receiver capacity),
# where the monoid admits the psum-scatter fast path, and which EDB slabs
# stream host-resident chunks through the fixpoint step.  Pinned straight
# through plan_program so no device mesh is needed.

_X_PREDICATES = {"edge": (2, 3e6), "tc": (2, 1e7), "rank": (1, 16384.0)}
_X_KW = dict(
    predicates=_X_PREDICATES,
    storage={"rank": "row-table"},
    exchange_ops={"tc": None, "rank": "sum"},
    edb=("edge",),
    row_value_cols={"edge": 0},
)


def test_exchange_and_chunking_plan_notes_golden():
    from repro.core.planner import plan_program

    plan = plan_program(
        (("rank", "tc"),), (), 1 << 20, MeshSpec((("data", 8),)),
        hbm_budget=1 << 22, **_X_KW,
    )
    # The 3M-row EDB slab holds every row (4M-row cap), so the budget sees
    # its true 36 MiB and streams it in 9 chunks of under 4 MiB each.
    assert plan.notes == (
        "storage-selection(n=1048576, edge=row-table[cap=4194304], "
        "rank=row-table[cap=131072], tc=row-table[cap=1048576])",
        "loop-invariant-caching(edb-grids)",
        "spmd(gspmd data-parallel x8)",
        "exchange(edge: bucket-a2a[cap=4194304])",
        "exchange(rank: psum-scatter)",
        "exchange(tc: bucket-a2a[cap=1048576])",
        "chunking(edge: 9 chunks, budget=4194304B)",
    ), plan.notes
    assert plan.exchanges == {
        "edge": "bucket-a2a", "rank": "psum-scatter", "tc": "bucket-a2a"}
    assert plan.exchange_caps == {
        "edge": 4194304, "rank": 16384, "tc": 1048576}
    assert plan.chunks == {"edge": 9}


def test_single_shard_plan_has_no_exchange_notes():
    """dp=1 (and dp>1 under the default HBM budget) must not grow new
    notes — every pre-PR-10 golden snapshot above stays byte-identical."""

    from repro.core.planner import plan_program

    plan = plan_program(
        (("rank", "tc"),), (), 1 << 20, MESHES["1way"], **_X_KW,
    )
    assert not any(
        n.startswith(("exchange(", "chunking(")) for n in plan.notes
    ), plan.notes
    assert plan.exchanges == {} and plan.chunks == {}


def test_exchange_caps_divide_estimate_by_shard_count():
    """The bucket-a2a receiver capacity is sized from the planner's global
    cardinality estimate divided across the data shards (then rounded to a
    power of two, clamped to the slab cap) — more shards, smaller
    per-shard buckets."""

    from repro.core.planner import plan_program

    caps = {
        dp: plan_program(
            (("rank", "tc"),), (), 1 << 20, MeshSpec((("data", dp),)),
            **_X_KW,
        ).exchange_caps["rank"]
        for dp in (2, 4, 8)
    }
    assert caps == {2: 65536, 4: 32768, 8: 16384}


def test_parsed_program_rewrite_structure_golden():
    for key, ex in _parsed_executables(rewrite=True).items():
        name, semi_naive = key
        want = GENERIC_REWRITE_STRUCTURE.get(name)
        if want is None or semi_naive:
            continue
        got = {df.label: df.structure() for df in ex.logical.body}
        for label, structure in want.items():
            assert got[label] == structure, (name, label, got[label])
