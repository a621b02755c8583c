"""Unified logical-plan executor: one engine for every XY-stratified program.

The paper's thesis is that many ML systems compile to recursive queries
executed by "a single unified data-parallel query processing engine".  This
module makes the :class:`~repro.core.algebra.LogicalPlan` that engine's real
execution contract:

* :func:`compile_program` executes **arbitrary** XY-stratified programs —
  transitive closure, connected components, same-generation, multi-stratum
  pipelines (see :mod:`repro.core.listings`) — by interpreting the algebra
  DAG per-stratum, driven by :func:`~repro.core.stratify.iteration_schedule`
  and :func:`~repro.core.stratify.fixpoint_phases` under
  :func:`~repro.core.fixpoint.device_fixpoint` /
  :class:`~repro.core.fixpoint.HostFixpointDriver`.

* The two paper listings keep their specialized fast paths (semi-naive
  sparse supersteps, ``fused_got_exchange``, reduce-tree schedules) as
  planner-selected operator implementations: :func:`build_pregel_steps` and
  :func:`build_imru_step` hold the shard_map / exchange machinery that
  ``compile_pregel`` and ``compile_imru`` lower through, and
  :func:`compile_program` routes Listing-1/2 programs (with their vectorized
  UDF bindings) onto exactly those pipelines.

Generic operator → physical mapping (the dense-grid backend):

=============  ==========================================================
logical op     physical implementation
=============  ==========================================================
ScanEDB        loop-invariant cached dense grid (device-resident EDB)
ScanState      carried-state read (this iteration's frontier)
Frontier       direct read of the newest materialized state (L4/L5)
Delta          delta-frontier read (semi-naive: changed facts only)
Join/Cross     broadcast-aligned grid intersection; shared value columns
               become equality masks (the index-probe analogue)
Apply          vectorized UDF over grid cells
GroupBy        Fig.-9 receiver combine via the CombineMonoid registry:
               masked dense reduction (hardware fast-path monoids) or the
               pre-clustered segmented scan (generic monoids) — selection
               recorded in ``plan.notes``
Select         masked comparison
AntiJoin       negated match mask (dense anti-semijoin)
Project        presence-OR over eliminated grid axes
Extend         broadcast constant column
Unnest         Listing-1 fast path only (vectorized message slabs)
=============  ==========================================================

Relations live on a dense vertex-domain grid ``[0, n)``: a predicate with
``k`` key (integer) columns materializes as a bool presence grid
``[n]^k`` plus one float grid per value column.  Dense grids are the
TPU-native formulation — every rule firing is a fused masked tensor
contraction, and on an SPMD mesh the grids shard over the data axes with
GSPMD inserting the exchanges.
"""

from __future__ import annotations

import ast
import functools
import time
from dataclasses import dataclass, field, replace
from typing import (
    Any, Callable, Dict, FrozenSet, List, Mapping, Optional, Sequence, Tuple,
)

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import algebra, stratify
from repro.core.datalog import Const, Program
from repro.core.fixpoint import (
    DriverConfig,
    FixpointResult,
    HostFixpointDriver,
    device_fixpoint,
    jit_hoisted,
)
from repro.core.hardware import MeshSpec, TPU_V5E, HardwareSpec
from repro.core.monoid import MonoidError, get_monoid
from repro.core.physical import (
    compact_active_edges,
    dense_psum_exchange,
    difference_row_codes,
    fused_got_exchange,
    grid_to_rows,
    hash_sort_exchange,
    join_row_codes,
    merging_exchange,
    reduce_tree,
    row_codes,
    row_hash_exchange,
    row_linear_index,
    rows_to_grid,
    segment_combine_sorted,
    sort_row_codes,
    sparse_hash_sort_exchange,
    sparse_merging_exchange,
    unique_row_runs,
)
from repro.core.planner import GroupBySpec, plan_program

__all__ = [
    "ExecutorError",
    "Relation",
    "RowRelation",
    "GenericExecutable",
    "compile_program",
    "PregelStepBundle",
    "build_pregel_steps",
    "build_imru_step",
]


class ExecutorError(Exception):
    """A program cannot be executed by the generic dense-grid backend."""


class _RowCapacityOverflow(Exception):
    """A row-table slab overflowed its static capacity mid-run; the caller
    falls back to the (lossless) dense-grid storage."""


# ---------------------------------------------------------------------------
# Dense-grid relations
# ---------------------------------------------------------------------------


@dataclass
class Relation:
    """A dense-grid relation instance over the vertex domain ``[0, n)``.

    ``key_positions`` lists the argument positions (after dropping any
    temporal argument) that index the grid; every other position is a value
    column stored as a float grid of the same shape.  ``present`` marks the
    tuples that exist.
    """

    n: int
    key_positions: Tuple[int, ...]
    present: Any
    values: Dict[int, Any] = field(default_factory=dict)

    @property
    def arity(self) -> int:
        return len(self.key_positions) + len(self.values)

    def count(self) -> int:
        return int(jnp.sum(self.present))

    def tuples(self) -> np.ndarray:
        """The present key tuples as an int array [count, n_keys]."""

        return np.argwhere(np.asarray(self.present))

    @classmethod
    def from_columns(cls, n: int, *cols) -> "Relation":
        """Build a relation from positional tuple columns.

        Integer-dtype columns are vertex-domain keys; floating columns are
        values.  Duplicate key tuples keep the last value row (EDB inputs
        with value columns should be key-unique).
        """

        arrs = [np.asarray(c) for c in cols]
        key_positions = tuple(
            i for i, c in enumerate(arrs)
            if np.issubdtype(c.dtype, np.integer)
        )
        keys = [arrs[i].astype(np.int64) for i in key_positions]
        _check_vertex_ids(n, key_positions, keys)
        k = len(keys)
        idx = tuple(keys)
        present = np.zeros((n,) * k, bool)
        if k:
            present[idx] = True
        else:
            present = np.asarray(bool(len(arrs) == 0 or arrs[0].size))
        values: Dict[int, Any] = {}
        for i, c in enumerate(arrs):
            if i in key_positions:
                continue
            grid = np.zeros((n,) * k, np.float32)
            if k:
                grid[idx] = c.astype(np.float32)
            else:
                grid = np.asarray(c[-1], np.float32) if c.size else grid
            values[i] = grid
        return cls(
            n=n,
            key_positions=key_positions,
            present=jnp.asarray(present),
            values={i: jnp.asarray(g) for i, g in values.items()},
        )


def _check_vertex_ids(n: int, key_positions, key_cols) -> None:
    """Fail loudly on out-of-domain / negative vertex ids (they would
    silently index-wrap into the dense grid or corrupt row codes)."""

    for pos, col in zip(key_positions, key_cols):
        if col.size == 0:
            continue
        lo, hi = int(col.min()), int(col.max())
        if lo < 0 or hi >= n:
            raise ExecutorError(
                f"key column {pos}: vertex id {lo if lo < 0 else hi} is "
                f"outside the domain [0, {n})"
            )


@dataclass
class RowRelation:
    """A sparse row-table relation: explicit key-tuple rows over ``[0, n)``.

    The row-table counterpart of :class:`Relation` — used when the dense
    ``n^k`` grid of an EDB would be infeasible (e.g. 64k-vertex sparse
    edges).  ``rows`` holds the distinct key tuples ``int32 [count, k]`` in
    lexicographic order; each value column is a ``float32 [count]`` array
    aligned with ``rows``.  The planner forces ``row-table`` storage for
    predicates bound to a ``RowRelation``.
    """

    n: int
    key_positions: Tuple[int, ...]
    rows: np.ndarray
    values: Dict[int, np.ndarray] = field(default_factory=dict)

    @property
    def arity(self) -> int:
        return len(self.key_positions) + len(self.values)

    def count(self) -> int:
        return int(self.rows.shape[0])

    def tuples(self) -> np.ndarray:
        """The key tuples as an int array [count, n_keys] (lex-sorted, the
        same order :meth:`Relation.tuples` produces)."""

        return np.array(self.rows, copy=True)

    @classmethod
    def from_columns(cls, n: int, *cols) -> "RowRelation":
        """Build a row-table relation from positional tuple columns.

        Same column typing as :meth:`Relation.from_columns` (integer dtype =
        key, floating = value); rows are deduplicated (last value row wins)
        and out-of-domain ids fail loudly.
        """

        arrs = [np.asarray(c) for c in cols]
        key_positions = tuple(
            i for i, c in enumerate(arrs)
            if np.issubdtype(c.dtype, np.integer)
        )
        if not key_positions:
            raise ExecutorError(
                "RowRelation needs at least one integer key column (use "
                "Relation for arity-0 / pure-value predicates)"
            )
        keys = [arrs[i].astype(np.int64) for i in key_positions]
        _check_vertex_ids(n, key_positions, keys)
        rows = np.stack(keys, axis=-1).astype(np.int32) if keys[0].size \
            else np.zeros((0, len(keys)), np.int32)
        # Keep-last dedupe: unique over the reversed rows keeps the last
        # occurrence of each key tuple, then re-sorts lexicographically.
        uniq, idx_rev = np.unique(rows[::-1], axis=0, return_index=True)
        src = rows.shape[0] - 1 - idx_rev
        values = {
            i: np.asarray(arrs[i], np.float32)[src]
            for i in range(len(arrs)) if i not in key_positions
        }
        return cls(n=n, key_positions=key_positions, rows=uniq,
                   values=values)

    def to_dense(self) -> Relation:
        """Materialize onto the dense grid (differential-test helper; only
        feasible for small domains)."""

        k = self.rows.shape[1]
        cols: List[np.ndarray] = []
        j = 0
        for i in range(self.arity):
            if i in self.key_positions:
                cols.append(self.rows[:, j].astype(np.int64))
                j += 1
            else:
                cols.append(self.values[i])
        return Relation.from_columns(self.n, *cols)


# Raw tuple arrays whose dense grid would exceed this many cells route to
# RowRelation automatically (the planner then keeps the predicate on
# row-table storage).
_DENSE_REL_CELL_LIMIT = 1 << 24

# Row-table GroupBy lowers through the dense grid-reduce (bit-identical to
# the dense engine) while the child's grid stays at most this many cells;
# beyond it the segmented sorted-combine path runs instead.
_GROUPBY_GRID_CELLS = 1 << 20


def _as_relation(name: str, value, domain: Optional[int]):
    if isinstance(value, (Relation, RowRelation)):
        return value
    arr = np.asarray(value)
    if domain is None:
        raise ExecutorError(
            f"relation {name!r} given as a raw array needs an explicit "
            "domain= (or pass a Relation built with Relation.from_columns)"
        )
    if arr.ndim == 2 and np.issubdtype(arr.dtype, np.integer):
        cols = tuple(arr[:, i] for i in range(arr.shape[1]))
        if arr.shape[1] and float(domain) ** arr.shape[1] > _DENSE_REL_CELL_LIMIT:
            return RowRelation.from_columns(domain, *cols)
        return Relation.from_columns(domain, *cols)
    raise ExecutorError(
        f"relation {name!r}: pass a Relation or an int tuple array [rows, arity]"
    )


# ---------------------------------------------------------------------------
# Operator interpreter — intermediates and helpers
# ---------------------------------------------------------------------------


@dataclass
class _Inter:
    """An intermediate result: a presence grid over ``dims`` (variable
    names, one grid axis each) plus full-shape value columns."""

    dims: Tuple[str, ...]
    present: Any
    cols: Dict[str, Any]


def _align(a, dims: Tuple[str, ...], out_dims: Tuple[str, ...]):
    """Transpose + reshape a grid with axes ``dims`` into the axis order of
    ``out_dims`` (size-1 axes for dims the grid does not carry)."""

    order = [dims.index(d) for d in out_dims if d in dims]
    a = jnp.transpose(a, order)
    shape: List[int] = []
    i = 0
    for d in out_dims:
        if d in dims:
            shape.append(a.shape[i])
            i += 1
        else:
            shape.append(1)
    return a.reshape(tuple(shape))


def _dim_grid(n: int, out_dims: Tuple[str, ...], d: str):
    ax = out_dims.index(d)
    shape = [1] * len(out_dims)
    shape[ax] = n
    return jnp.arange(n, dtype=jnp.int32).reshape(shape)


_CMP = {
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}


def _monoid_for(agg: str):
    try:
        return get_monoid(agg)
    except MonoidError as err:
        raise ExecutorError(
            f"aggregate {agg!r} is not a registered CombineMonoid — the "
            "generic executor resolves head aggregates through the monoid "
            "registry (repro.core.monoid.register_monoid)"
        ) from err


@dataclass
class _Ctx:
    """Evaluation context for one rule firing."""

    program: Program
    n: int
    sigs: Mapping[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]
    relations: Mapping[str, Relation]
    state: Mapping[str, Mapping[str, Any]]
    views: Dict[str, Dict[str, Any]]
    materialized: Mapping[str, Dict[str, Any]]
    connectors: Mapping[str, str]
    j: Any
    label: str = ""
    # CSE support: ids of canonical shared subtrees (from the rewrite pass)
    # and the per-context memo of their evaluated grids.  Sound because only
    # EDB-pure subtrees are shared — their inputs never change within a step.
    shared: FrozenSet[int] = frozenset()
    memo: Dict[int, Any] = field(default_factory=dict)
    # Row-table storage: per-predicate selection ("dense-grid"/"row-table"),
    # per-predicate slab capacities, the shared intermediate capacity, the
    # precomputed row-table EDB slabs, and the traced overflow flags this
    # firing accumulated (checked by the overflow policy).
    storage: Mapping[str, str] = field(default_factory=dict)
    row_caps: Mapping[str, int] = field(default_factory=dict)
    row_cap: int = 0
    row_edb: Mapping[str, Dict[str, Any]] = field(default_factory=dict)
    overflow: List[Any] = field(default_factory=list)
    # Joins that read a row-table EDB slab get one pair slot per slab row
    # (off when the caller pins ``row_cap``; see ``_pair_cap``).
    edb_join_rows: bool = False
    # Explicit sharded exchanges: the planner's per-predicate connector
    # selection + receiver caps, the head predicate of the firing rule (the
    # selection key), and the mesh/data-axes the shard_map lowering targets.
    exchanges: Mapping[str, str] = field(default_factory=dict)
    exchange_caps: Mapping[str, int] = field(default_factory=dict)
    exchange_target: str = ""
    mesh: Optional[Any] = None
    batch_axes: Tuple[str, ...] = ()
    # Out-of-core streaming: EDB predicates whose slabs are host-resident
    # chunk lists — their scans may only fire under a chunk overlay
    # (``row_edb`` rebound to one chunk inside the streaming loop).
    chunked: FrozenSet[str] = frozenset()


def _read_pred(ctx: _Ctx, name: str) -> Dict[str, Any]:
    if name in ctx.state:
        return ctx.state[name]
    if name in ctx.views:
        return ctx.views[name]
    if name in ctx.materialized:
        return ctx.materialized[name]
    raise ExecutorError(
        f"rule {ctx.label or '?'}: predicate {name!r} read before any rule "
        "materialized it (check the fixpoint-phase ordering)"
    )


def _scan_inter(columns, key_positions, present, values_by_pos) -> _Inter:
    dims = tuple(columns[p] for p in key_positions)
    cols = {}
    for p, grid in values_by_pos.items():
        cols[columns[int(p)]] = grid
    return _Inter(dims, present, cols)


def _scan_rows(columns, key_positions, ids, valid, values_by_pos):
    dims = tuple(columns[p] for p in key_positions)
    cols = {}
    for p, col in values_by_pos.items():
        cols[columns[int(p)]] = col
    return _Rows(dims, ids, valid, cols)


def _operand(inter: _Inter, x, n: int, j):
    if isinstance(x, Const):
        if not isinstance(x.value, (int, float, bool)):
            raise ExecutorError(
                f"non-numeric constant {x.value!r} is not executable on the "
                "dense-grid backend"
            )
        return jnp.asarray(x.value)
    if x in inter.cols:
        return inter.cols[x]
    if x in inter.dims:
        return _dim_grid(n, inter.dims, x)
    if x == "J":
        return j
    raise ExecutorError(f"unbound column {x!r} in comparison/UDF input")


def _join(l: _Inter, r: _Inter, keys: Tuple[str, ...], n: int) -> _Inter:
    out_dims = l.dims + tuple(d for d in r.dims if d not in l.dims)
    shape = (n,) * len(out_dims)

    def al(g, dims):
        return jnp.broadcast_to(_align(g, dims, out_dims), shape)

    present = jnp.logical_and(al(l.present, l.dims), al(r.present, r.dims))
    for key in keys:
        l_dim, r_dim = key in l.dims, key in r.dims
        if l_dim and r_dim:
            continue  # shared grid axis: equality is structural
        lv, rv = l.cols.get(key), r.cols.get(key)
        if l_dim and rv is not None:
            present = jnp.logical_and(
                present, al(rv, r.dims) == _dim_grid(n, out_dims, key)
            )
        elif r_dim and lv is not None:
            present = jnp.logical_and(
                present, al(lv, l.dims) == _dim_grid(n, out_dims, key)
            )
        elif lv is not None and rv is not None:
            present = jnp.logical_and(
                present, al(lv, l.dims) == al(rv, r.dims)
            )
    cols: Dict[str, Any] = {}
    for c, g in l.cols.items():
        if c not in out_dims:
            cols[c] = al(g, l.dims)
    for c, g in r.cols.items():
        if c not in cols and c not in out_dims:
            cols[c] = al(g, r.dims)
    return _Inter(out_dims, present, cols)


# ---------------------------------------------------------------------------
# Row-table operators (the sparse storage backend)
# ---------------------------------------------------------------------------


@dataclass
class _Rows:
    """A row-table intermediate: padded id columns ``int32[cap, k]`` (one
    column per dim), a slot validity mask, and per-row value columns.
    Invariant: valid rows are unique by their dim tuple (scans read deduped
    tables; join/select/project preserve or restore uniqueness), so value
    scatters and representative-first merges are exact."""

    dims: Tuple[str, ...]
    ids: Any
    valid: Any
    cols: Dict[str, Any]


def _codes_for(rows: _Rows, dims: Tuple[str, ...], n: int):
    """uint32 row codes of a dim subset (shared-key encoding for joins)."""

    cap = rows.ids.shape[0]
    if not dims:
        return jnp.zeros((cap,), jnp.uint32)
    sub = jnp.stack([rows.ids[:, rows.dims.index(d)] for d in dims], axis=-1)
    try:
        return row_codes(sub, n)
    except ValueError as err:
        raise ExecutorError(str(err)) from err


def _operand_rows(rows: _Rows, x, ctx: _Ctx):
    if isinstance(x, Const):
        if not isinstance(x.value, (int, float, bool)):
            raise ExecutorError(
                f"non-numeric constant {x.value!r} is not executable on the "
                "row-table backend"
            )
        return jnp.asarray(x.value)
    if x in rows.cols:
        return rows.cols[x]
    if x in rows.dims:
        return rows.ids[:, rows.dims.index(x)]
    if x == "J":
        return ctx.j
    raise ExecutorError(f"unbound column {x!r} in comparison/UDF input")


def _inter_to_rows(inter: _Inter, ctx: _Ctx) -> _Rows:
    """``to_rows`` boundary converter: compact a dense intermediate into a
    row table (inserted automatically where mixed-storage operators meet)."""

    k = len(inter.dims)
    cells = int(ctx.n) ** k
    cap = cells if 0 < cells <= max(ctx.row_cap, 1) else max(ctx.row_cap, 1)
    ids, valid, lin, ov = grid_to_rows(inter.present, cap)
    ctx.overflow.append(ov)
    cols = {
        c: jnp.reshape(g, (-1,))[lin] for c, g in inter.cols.items()
    }
    return _Rows(inter.dims, ids, valid, cols)


def _rows_to_inter(rows: _Rows, ctx: _Ctx) -> _Inter:
    """``to_grid`` boundary converter: scatter a row table back onto the
    dense vertex-domain grid (only at dense-stored materialization sites,
    where the planner already approved the grid size)."""

    n, k = ctx.n, len(rows.dims)
    if k == 0:
        pres = jnp.any(rows.valid)
        cols = {
            c: jnp.sum(jnp.where(rows.valid, g, jnp.zeros_like(g)))
            for c, g in rows.cols.items()
        }
        return _Inter((), pres, cols)
    size = n ** k
    lin = row_linear_index(rows.ids, rows.valid, n)
    present = jnp.zeros((size,), jnp.bool_).at[lin].set(
        True, mode="drop"
    ).reshape((n,) * k)
    cols = {}
    for c, g in rows.cols.items():
        g = jnp.broadcast_to(g, (rows.ids.shape[0],))
        cols[c] = jnp.zeros((size,), g.dtype).at[lin].set(
            g, mode="drop"
        ).reshape((n,) * k)
    return _Inter(rows.dims, present, cols)


def _coerce_pair(l, r, ctx: _Ctx):
    """Promote a mixed dense/row operand pair to row tables (the converter
    goes dense→rows: the row side may have no feasible grid)."""

    if isinstance(l, _Rows) or isinstance(r, _Rows):
        if not isinstance(l, _Rows):
            l = _inter_to_rows(l, ctx)
        if not isinstance(r, _Rows):
            r = _inter_to_rows(r, ctx)
        return l, r, True
    return l, r, False


def _residual_valid(l: _Rows, r: _Rows, keys, li, ri, valid):
    """Apply the non-structural join key conditions (value-column equality)
    per output slot — the row analogue of the dense `_join` masks."""

    for key in keys:
        l_dim, r_dim = key in l.dims, key in r.dims
        if l_dim and r_dim:
            continue  # shared id column: equality is in the row codes
        lv, rv = l.cols.get(key), r.cols.get(key)
        if l_dim and rv is not None:
            valid = jnp.logical_and(
                valid, rv[ri] == l.ids[:, l.dims.index(key)][li]
            )
        elif r_dim and lv is not None:
            valid = jnp.logical_and(
                valid, lv[li] == r.ids[:, r.dims.index(key)][ri]
            )
        elif lv is not None and rv is not None:
            valid = jnp.logical_and(valid, lv[li] == rv[ri])
    return valid


# ---------------------------------------------------------------------------
# Explicit sharded row exchanges (planner-selected connectors)
# ---------------------------------------------------------------------------


def _exchange_site(ctx: _Ctx):
    """The planner's explicit-exchange selection for the firing rule's head
    predicate, resolved against the live mesh: ``(mode, axes, n_shards)``,
    or ``None`` when the site stays on implicit GSPMD partitioning."""

    if ctx.mesh is None or not ctx.batch_axes:
        return None
    mode = ctx.exchanges.get(ctx.exchange_target)
    if mode in (None, "gspmd"):
        return None
    n_shards = int(np.prod([ctx.mesh.shape[a] for a in ctx.batch_axes]))
    if n_shards <= 1:
        return None
    return mode, ctx.batch_axes, n_shards


def _pad_lead(arr, pad: int):
    if pad == 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, widths)


def _groupby_rows_exchange(op: algebra.GroupBy, child: _Rows, ctx: _Ctx):
    """Lower a row-table GroupBy onto the explicit sharded connectors the
    Listing-1 fast path uses, instead of letting GSPMD partition the slab
    implicitly (cap-leading slabs replicate under the named-sharding rule,
    so implicit partitioning leaves every shard reducing the full slab).

    * ``bucket-a2a`` — each shard keeps a ``1/S`` slice of the input rows,
      hashes group keys to owner shards, ships ``(code, ids, val)`` through
      the key-hash bucket all-to-all, and the owner runs the pre-clustered
      segmented combine on its buckets; unique group rows compact into the
      planner's per-shard receiver cap (overflow-flagged, lossless dense
      fallback) and an all-gather replicates the result slab.
    * ``psum-scatter`` — monoid-admitted (``sum`` kernels on grids small
      enough to materialize): shards scatter-add local partials into a
      dense group grid and one ``psum`` combines them — no row traffic.

    Returns ``None`` when the site keeps the implicit lowering (the planner
    chose ``gspmd``, the mesh has no data axes, or the slab is degenerate).
    """

    site = _exchange_site(ctx)
    if site is None or not op.keys:
        return None
    mode, axes, n_shards = site
    cap = child.ids.shape[0]
    if cap < n_shards:
        return None
    from jax.experimental.shard_map import shard_map

    n = ctx.n
    vals = jnp.broadcast_to(_operand_rows(child, op.agg_col, ctx), (cap,))
    if not jnp.issubdtype(vals.dtype, jnp.floating):
        vals = vals.astype(jnp.float32)
    key_ids = jnp.stack(
        [child.ids[:, child.dims.index(k)] for k in op.keys], axis=-1
    )
    valid = child.valid
    pad = (-cap) % n_shards
    key_ids = _pad_lead(key_ids, pad)
    vals = _pad_lead(vals, pad)
    valid = _pad_lead(valid, pad)
    segments = n ** len(op.keys)
    monoid = _monoid_for(op.agg)
    if mode == "psum-scatter" and (
        monoid.kernel_op != "sum"
        or not 0 < segments <= _GROUPBY_GRID_CELLS
    ):
        mode = "bucket-a2a"  # forced override outside the mode's envelope

    if mode == "psum-scatter":
        def psum_fn(ids_l, vals_l, valid_l):
            lin = row_linear_index(ids_l, valid_l, n)
            part = jnp.zeros((segments,), jnp.float32).at[lin].add(
                jnp.where(valid_l, vals_l, 0.0), mode="drop"
            )
            cnt = jnp.zeros((segments,), jnp.int32).at[lin].add(
                valid_l.astype(jnp.int32), mode="drop"
            )
            return jax.lax.psum(part, axes), jax.lax.psum(cnt, axes)

        part, cnt = shard_map(
            psum_fn, mesh=ctx.mesh,
            in_specs=(P(axes), P(axes), P(axes)),
            out_specs=(P(), P()), check_rep=False,
        )(key_ids, vals, valid)
        shape = (n,) * len(op.keys)
        inter = _Inter(
            tuple(op.keys), (cnt > 0).reshape(shape),
            {op.out_col: part.reshape(shape)},
        )
        return _inter_to_rows(inter, ctx)

    ecap = int(ctx.exchange_caps.get(ctx.exchange_target, 0)) or cap
    codes = _codes_for(
        _Rows(tuple(op.keys), key_ids, valid, {}), tuple(op.keys), n
    )

    def bucket_fn(codes_l, ids_l, vals_l, valid_l):
        owner = (codes_l % jnp.uint32(n_shards)).astype(jnp.int32)
        shipped, valid_x, of1 = row_hash_exchange(
            owner, {"codes": codes_l, "ids": ids_l, "vals": vals_l},
            valid_l, n_shards, ecap, axes,
        )
        rcap = shipped["codes"].shape[0]
        perm, skey, n_valid = sort_row_codes(shipped["codes"], valid_x)
        is_new, seg = unique_row_runs(skey, n_valid)
        in_valid = jnp.arange(rcap, dtype=jnp.int32) < n_valid
        red = segment_combine_sorted(
            shipped["vals"][perm], seg, rcap, op.agg, edge_active=in_valid
        )
        idx, u_valid = compact_active_edges(is_new, ecap)
        of2 = jnp.sum(is_new.astype(jnp.int32)) > ecap
        take = jnp.minimum(idx, rcap - 1)
        out_ids = shipped["ids"][perm][take]
        out_val = red[seg][take]
        g_ids = jax.lax.all_gather(out_ids, axes, axis=0, tiled=True)
        g_valid = jax.lax.all_gather(u_valid, axes, axis=0, tiled=True)
        g_val = jax.lax.all_gather(out_val, axes, axis=0, tiled=True)
        of = jax.lax.psum(jnp.logical_or(of1, of2).astype(jnp.int32), axes)
        return g_ids, g_valid, g_val, of

    g_ids, g_valid, g_val, of = shard_map(
        bucket_fn, mesh=ctx.mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes)),
        out_specs=(P(), P(), P(), P()), check_rep=False,
    )(codes, key_ids, vals, valid)
    ctx.overflow.append(of > 0)
    return _Rows(tuple(op.keys), g_ids, g_valid, {op.out_col: g_val})


def _join_rows_exchange(l: _Rows, r: _Rows, keys, ctx: _Ctx, pair_cap: int):
    """Hash-partitioned sort-merge join inside ``shard_map``: both slabs
    split ``1/S`` per shard, rows ship to ``hash(shared-code) % S`` through
    the bucket all-to-all, each owner joins exactly its key partition (the
    partition is disjoint and complete, so the gathered union is the exact
    join), and pair capacity splits ``S`` ways per shard.  Returns ``None``
    when the site stays implicit (no shared dims, planner chose ``gspmd``,
    or psum-scatter — an aggregation-only connector)."""

    site = _exchange_site(ctx)
    if site is None:
        return None
    mode, axes, n_shards = site
    shared = tuple(d for d in l.dims if d in r.dims)
    if mode != "bucket-a2a" or not shared:
        return None
    lcap, rcap = l.ids.shape[0], r.ids.shape[0]
    if lcap < n_shards or rcap < n_shards:
        return None
    from jax.experimental.shard_map import shard_map

    n = ctx.n
    out_dims = l.dims + tuple(d for d in r.dims if d not in l.dims)
    ecap = int(ctx.exchange_caps.get(ctx.exchange_target, 0)) \
        or max(lcap, rcap)
    shard_pair_cap = -(-pair_cap // n_shards)

    def pack_side(rows: _Rows, cap: int):
        pad = (-cap) % n_shards
        codes = _codes_for(rows, shared, n)
        return {
            "codes": _pad_lead(codes, pad),
            "ids": _pad_lead(rows.ids, pad),
            "cols": {
                c: _pad_lead(jnp.broadcast_to(g, (cap,)), pad)
                for c, g in rows.cols.items()
            },
        }, _pad_lead(rows.valid, pad)

    l_in, l_valid = pack_side(l, lcap)
    r_in, r_valid = pack_side(r, rcap)

    def join_fn(l_t, lv, r_t, rv):
        lx, lvx, of_l = row_hash_exchange(
            (l_t["codes"] % jnp.uint32(n_shards)).astype(jnp.int32),
            l_t, lv, n_shards, ecap, axes,
        )
        rx, rvx, of_r = row_hash_exchange(
            (r_t["codes"] % jnp.uint32(n_shards)).astype(jnp.int32),
            r_t, rv, n_shards, ecap, axes,
        )
        li, ri, valid, of_j = join_row_codes(
            lx["codes"], lvx, rx["codes"], rvx, shard_pair_cap
        )
        l2 = _Rows(l.dims, lx["ids"], lvx, lx["cols"])
        r2 = _Rows(r.dims, rx["ids"], rvx, rx["cols"])
        valid = _residual_valid(l2, r2, keys, li, ri, valid)
        id_cols = []
        for d in out_dims:
            if d in l.dims:
                id_cols.append(l2.ids[:, l.dims.index(d)][li])
            else:
                id_cols.append(r2.ids[:, r.dims.index(d)][ri])
        ids = jnp.stack(id_cols, axis=-1)
        cols: Dict[str, Any] = {}
        for c, g in l2.cols.items():
            if c not in out_dims:
                cols[c] = g[li]
        for c, g in r2.cols.items():
            if c not in cols and c not in out_dims:
                cols[c] = g[ri]
        g_ids = jax.lax.all_gather(ids, axes, axis=0, tiled=True)
        g_valid = jax.lax.all_gather(valid, axes, axis=0, tiled=True)
        g_cols = {
            c: jax.lax.all_gather(g, axes, axis=0, tiled=True)
            for c, g in cols.items()
        }
        of = jax.lax.psum(
            (of_l | of_r | of_j).astype(jnp.int32), axes
        )
        return g_ids, g_valid, g_cols, of

    g_ids, g_valid, g_cols, of = shard_map(
        join_fn, mesh=ctx.mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes)),
        out_specs=(P(), P(), P(), P()), check_rep=False,
    )(l_in, l_valid, r_in, r_valid)
    ctx.overflow.append(of > 0)
    return _Rows(out_dims, g_ids, g_valid, g_cols)


def _pair_cap(op: algebra.LogicalOp, ctx: _Ctx) -> int:
    """A row join's pair capacity: the plan's intermediate cap, raised to
    the slab's row count when the join reads a row-table EDB slab (a join
    against a key-unique side yields one pair per slab row)."""

    cap = max(ctx.row_cap, 1)
    stack = [op] if ctx.edb_join_rows else []
    while stack:
        node = stack.pop()
        if isinstance(node, algebra.ScanEDB) and node.relation in ctx.row_edb:
            cap = max(cap, int(ctx.row_edb[node.relation]["ids"].shape[0]))
        stack.extend(node.children())
    return cap


def _join_rows(l: _Rows, r: _Rows, keys, ctx: _Ctx,
               pair_cap: Optional[int] = None) -> _Rows:
    """Sort-merge equi-join on the shared dims' row codes; pairs expand
    into ``pair_cap`` slots (default: the plan's intermediate capacity),
    overflow-flagged."""

    pair_cap = max(ctx.row_cap, 1) if pair_cap is None else pair_cap
    out = _join_rows_exchange(l, r, keys, ctx, pair_cap)
    if out is not None:
        return out
    n = ctx.n
    shared = tuple(d for d in l.dims if d in r.dims)
    out_dims = l.dims + tuple(d for d in r.dims if d not in l.dims)
    li, ri, valid, ov = join_row_codes(
        _codes_for(l, shared, n), l.valid,
        _codes_for(r, shared, n), r.valid, pair_cap,
    )
    ctx.overflow.append(ov)
    valid = _residual_valid(l, r, keys, li, ri, valid)
    id_cols = []
    for d in out_dims:
        if d in l.dims:
            id_cols.append(l.ids[:, l.dims.index(d)][li])
        else:
            id_cols.append(r.ids[:, r.dims.index(d)][ri])
    ids = jnp.stack(id_cols, axis=-1) if id_cols else \
        jnp.zeros((pair_cap, 0), jnp.int32)
    cols: Dict[str, Any] = {}
    for c, g in l.cols.items():
        if c not in out_dims:
            cols[c] = g[li]
    for c, g in r.cols.items():
        if c not in cols and c not in out_dims:
            cols[c] = g[ri]
    return _Rows(out_dims, ids, valid, cols)


def _antijoin_rows(l: _Rows, r: _Rows, keys, ctx: _Ctx) -> _Rows:
    """Exact set-difference on row tables: left rows whose shared-dim
    projection (plus any residual key conditions) has NO right match keep
    their slots; everything else is invalidated.  Replaces the dense
    backend's ones-presence join + any-mask hack."""

    n = ctx.n
    shared = tuple(d for d in l.dims if d in r.dims)
    residual = any(
        not (key in l.dims and key in r.dims) for key in keys
    )
    lc, rc = _codes_for(l, shared, n), _codes_for(r, shared, n)
    if not residual:
        keep = difference_row_codes(lc, l.valid, rc, r.valid)
        return _Rows(l.dims, l.ids, keep, l.cols)
    # Residual value conditions: probe via the pair expansion, then mark
    # left rows with any surviving match.
    cap_l = lc.shape[0]
    li, ri, valid, ov = join_row_codes(
        lc, l.valid, rc, r.valid, max(ctx.row_cap, 1)
    )
    ctx.overflow.append(ov)
    valid = _residual_valid(l, r, keys, li, ri, valid)
    li_d = jnp.where(valid, li, cap_l)
    matched = jnp.zeros((cap_l,), jnp.bool_).at[li_d].set(
        True, mode="drop"
    )
    keep = jnp.logical_and(l.valid, jnp.logical_not(matched))
    return _Rows(l.dims, l.ids, keep, l.cols)


def _project_rows(op: algebra.Project, child: _Rows, ctx: _Ctx) -> _Rows:
    cols = {c: child.cols[c] for c in op.columns if c in child.cols}
    keep = tuple(d for d in child.dims if d in op.columns)
    dropped = len(keep) != len(child.dims)
    if not dropped:
        return _Rows(child.dims, child.ids, child.valid, cols)
    if cols:
        raise ExecutorError(
            f"rule {ctx.label or '?'}: projecting away grid dimensions "
            "under value columns requires a head aggregate"
        )
    # Dropping dims can alias rows: dedupe by sorting the projected codes
    # and keeping first occurrences (set semantics restored).
    kept_ids = jnp.stack(
        [child.ids[:, child.dims.index(d)] for d in keep], axis=-1
    ) if keep else jnp.zeros((child.ids.shape[0], 0), jnp.int32)
    codes = _codes_for(_Rows(keep, kept_ids, child.valid, {}), keep, ctx.n)
    perm, skey, n_valid = sort_row_codes(codes, child.valid)
    is_new, _ = unique_row_runs(skey, n_valid)
    return _Rows(keep, kept_ids[perm], is_new, {})


def _groupby_rows(op: algebra.GroupBy, child: _Rows, ctx: _Ctx) -> _Rows:
    n = ctx.n
    for k in op.keys:
        if k not in child.dims:
            raise ExecutorError(
                f"rule {ctx.label or '?'}: group key {k!r} must be a "
                "vertex-domain column"
            )
    monoid = _monoid_for(op.agg)
    if monoid.structured:
        raise ExecutorError(
            f"structured monoid {op.agg!r} needs width-typed payload slabs; "
            "the row-table backend aggregates scalar cells"
        )
    if monoid.finalize is not None:
        raise ExecutorError(
            f"monoid {op.agg!r} carries a finalize step; the row-table "
            "backend only supports plain accumulator monoids"
        )
    out = _groupby_rows_exchange(op, child, ctx)
    if out is not None:
        return out
    cells = float(n) ** len(child.dims)
    if 0 < cells <= _GROUPBY_GRID_CELLS:
        # Lower through the dense grid-reduce when the child's grid is
        # small: rows are unique-by-dims so the scatter is exact, and the
        # reduction then performs the same adds in the same order as the
        # dense engine — forced-row runs match dense bit-for-bit instead
        # of drifting by summation-order ULPs.  Large domains take the
        # segmented path below.
        return _inter_to_rows(
            _groupby(op, _rows_to_inter(child, ctx), ctx), ctx
        )
    cap = child.ids.shape[0]
    vals = jnp.broadcast_to(_operand_rows(child, op.agg_col, ctx), (cap,))
    if not jnp.issubdtype(vals.dtype, jnp.floating):
        vals = vals.astype(jnp.float32)
    key_ids = jnp.stack(
        [child.ids[:, child.dims.index(k)] for k in op.keys], axis=-1
    ) if op.keys else jnp.zeros((cap, 0), jnp.int32)
    codes = _codes_for(_Rows(tuple(op.keys), key_ids, child.valid, {}),
                       tuple(op.keys), n)
    perm, skey, n_valid = sort_row_codes(codes, child.valid)
    is_new, seg = unique_row_runs(skey, n_valid)
    in_valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
    # Pre-clustered segmented path: rows arrive sorted by group code, so
    # segment ids are sorted and the combine is one scan.
    red = segment_combine_sorted(
        vals[perm], seg, cap, op.agg, edge_active=in_valid
    )
    return _Rows(
        tuple(op.keys), key_ids[perm], is_new, {op.out_col: red[seg]}
    )


def _eval(op: algebra.LogicalOp, ctx: _Ctx) -> _Inter:
    if ctx.shared and id(op) in ctx.shared:
        hit = ctx.memo.get(id(op))
        if hit is None:
            hit = _eval_inner(op, ctx)
            ctx.memo[id(op)] = hit
        return hit
    return _eval_inner(op, ctx)


def _eval_inner(op: algebra.LogicalOp, ctx: _Ctx):
    n = ctx.n
    if isinstance(op, algebra.ScanEDB):
        if op.relation == "__unit__":
            return _Inter((), jnp.asarray(True), {})
        if op.relation in ctx.row_edb:
            tbl = ctx.row_edb[op.relation]
            rel = ctx.relations[op.relation]
            dims = tuple(op.columns[p] for p in rel.key_positions)
            cols = {op.columns[int(p)]: g for p, g in tbl["values"].items()}
            return _Rows(dims, tbl["ids"], tbl["valid"], cols)
        if op.relation in ctx.chunked:
            raise ExecutorError(
                f"chunked EDB {op.relation!r} scanned outside a chunk "
                "overlay — out-of-core slabs stream through the host chunk "
                "loop only (fail closed)"
            )
        rel = ctx.relations[op.relation]
        if isinstance(rel, RowRelation):
            raise ExecutorError(
                f"EDB {op.relation!r} is a RowRelation but was planned onto "
                "dense-grid storage (its grid is infeasible) — leave its "
                "storage selection to the planner"
            )
        return _scan_inter(op.columns, rel.key_positions, rel.present, rel.values)
    if isinstance(op, algebra.Delta):
        entry = _read_pred(ctx, op.relation)
        keys, _ = ctx.sigs[op.relation]
        if "ids" in entry:
            return _scan_rows(
                op.columns, keys, entry["ids"],
                entry.get("delta", entry["present"]), entry["values"],
            )
        return _scan_inter(
            op.columns, keys, entry.get("delta", entry["present"]),
            entry["values"],
        )
    if isinstance(op, (algebra.ScanState, algebra.ScanView, algebra.Frontier)):
        entry = _read_pred(ctx, op.relation)
        keys, _ = ctx.sigs[op.relation]
        if "ids" in entry:
            return _scan_rows(
                op.columns, keys, entry["ids"], entry["present"],
                entry["values"],
            )
        return _scan_inter(op.columns, keys, entry["present"], entry["values"])
    if isinstance(op, algebra.Join):
        l, r, rowmode = _coerce_pair(
            _eval(op.left, ctx), _eval(op.right, ctx), ctx
        )
        if rowmode:
            return _join_rows(l, r, op.keys, ctx, _pair_cap(op, ctx))
        return _join(l, r, op.keys, n)
    if isinstance(op, algebra.Cross):
        l, r, rowmode = _coerce_pair(
            _eval(op.left, ctx), _eval(op.right, ctx), ctx
        )
        if rowmode:
            return _join_rows(l, r, (), ctx, _pair_cap(op, ctx))
        return _join(l, r, (), n)
    if isinstance(op, algebra.AntiJoin):
        l, r, rowmode = _coerce_pair(
            _eval(op.left, ctx), _eval(op.right, ctx), ctx
        )
        if rowmode:
            return _antijoin_rows(l, r, op.keys, ctx)
        joined = _join(
            _Inter(l.dims, jnp.ones_like(l.present), l.cols), r, op.keys, n
        )
        extra = tuple(
            joined.dims.index(d) for d in joined.dims if d not in l.dims
        )
        match = jnp.any(joined.present, axis=extra) if extra else joined.present
        return _Inter(l.dims, jnp.logical_and(l.present, ~match), l.cols)
    if isinstance(op, algebra.Select):
        child = _eval(op.child, ctx)
        if isinstance(child, _Rows):
            lhs = _operand_rows(child, op.lhs, ctx)
            rhs = _operand_rows(child, op.rhs, ctx)
            mask = _CMP[op.op](lhs, rhs)
            return _Rows(
                child.dims, child.ids,
                jnp.logical_and(child.valid, mask), child.cols,
            )
        lhs = _operand(child, op.lhs, n, ctx.j)
        rhs = _operand(child, op.rhs, n, ctx.j)
        mask = _CMP[op.op](lhs, rhs)
        return _Inter(
            child.dims, jnp.logical_and(child.present, mask), child.cols
        )
    if isinstance(op, algebra.Project):
        child = _eval(op.child, ctx)
        if isinstance(child, _Rows):
            return _project_rows(op, child, ctx)
        cols = {c: child.cols[c] for c in op.columns if c in child.cols}
        keep = tuple(d for d in child.dims if d in op.columns)
        drop = tuple(child.dims.index(d) for d in child.dims if d not in keep)
        if drop and cols:
            raise ExecutorError(
                f"rule {ctx.label or '?'}: projecting away grid dimensions "
                "under value columns requires a head aggregate"
            )
        present = jnp.any(child.present, axis=drop) if drop else child.present
        if drop:
            cols = {}
        return _Inter(keep, present, cols)
    if isinstance(op, algebra.Extend):
        child = _eval(op.child, ctx)
        if not isinstance(op.value, (int, float, bool)):
            raise ExecutorError(
                f"non-numeric head constant {op.value!r} is not executable "
                "on the dense-grid backend"
            )
        if isinstance(child, _Rows):
            cols = dict(child.cols)
            cols[op.column] = jnp.full(
                (child.ids.shape[0],), op.value, jnp.float32
            )
            return _Rows(child.dims, child.ids, child.valid, cols)
        shape = (n,) * len(child.dims)
        cols = dict(child.cols)
        cols[op.column] = jnp.broadcast_to(
            jnp.asarray(op.value, jnp.float32), shape
        )
        return _Inter(child.dims, child.present, cols)
    if isinstance(op, algebra.Apply):
        child = _eval(op.child, ctx)
        udf = ctx.program.udfs.get(op.fn)
        if udf is None or udf.fn is None:
            raise ExecutorError(f"UDF {op.fn!r} has no bound implementation")
        rowmode = isinstance(child, _Rows)
        args = []
        for c in op.in_cols:
            if isinstance(c, str) and c.startswith("lit:"):
                args.append(ast.literal_eval(c[4:]))
            elif rowmode:
                args.append(_operand_rows(child, c, ctx))
            else:
                args.append(_operand(child, c, n, ctx.j))
        outs = udf.fn(*args)
        if not isinstance(outs, tuple):
            outs = (outs,)
        if len(outs) != len(op.out_cols):
            raise ExecutorError(
                f"UDF {op.fn!r} returned {len(outs)} outputs, rule binds "
                f"{len(op.out_cols)}"
            )
        if rowmode:
            cols = dict(child.cols)
            for name, o in zip(op.out_cols, outs):
                cols[name] = jnp.broadcast_to(
                    jnp.asarray(o), (child.ids.shape[0],)
                )
            return _Rows(child.dims, child.ids, child.valid, cols)
        shape = (n,) * len(child.dims)
        cols = dict(child.cols)
        for name, o in zip(op.out_cols, outs):
            cols[name] = jnp.broadcast_to(jnp.asarray(o), shape)
        return _Inter(child.dims, child.present, cols)
    if isinstance(op, algebra.GroupBy):
        child = _eval(op.child, ctx)
        if isinstance(child, _Rows):
            return _groupby_rows(op, child, ctx)
        return _groupby(op, child, ctx)
    if isinstance(op, algebra.Unnest):
        raise ExecutorError(
            "set-valued unnesting (rule L8) is a Listing-1 construct: bind "
            "the vectorized VertexProgram front-end (compile_program with "
            "binding=) instead of the generic dense-grid backend"
        )
    raise ExecutorError(f"unsupported logical operator {type(op).__name__}")


def _groupby(op: algebra.GroupBy, child: _Inter, ctx: _Ctx) -> _Inter:
    n = ctx.n
    for k in op.keys:
        if k not in child.dims:
            raise ExecutorError(
                f"rule {ctx.label or '?'}: group key {k!r} must be a "
                "vertex-domain column"
            )
    monoid = _monoid_for(op.agg)
    if monoid.structured:
        raise ExecutorError(
            f"structured monoid {op.agg!r} needs width-typed payload slabs; "
            "the dense-grid backend aggregates scalar cells"
        )
    if monoid.finalize is not None:
        # Fail closed: the grid backend has no single finalize seam (rule
        # outputs for one target union-merge across rules), so a
        # finalize-bearing accumulator would leak unfinalized values.
        raise ExecutorError(
            f"monoid {op.agg!r} carries a finalize step; the dense-grid "
            "backend only supports plain accumulator monoids"
        )
    elim = tuple(d for d in child.dims if d not in op.keys)
    vals = _operand(child, op.agg_col, n, ctx.j)
    vals = jnp.broadcast_to(vals, (n,) * len(child.dims))
    if not jnp.issubdtype(vals.dtype, jnp.floating):
        vals = vals.astype(jnp.float32)
    ident = jnp.asarray(float(monoid.identity), vals.dtype)
    masked = jnp.where(child.present, vals, ident)
    perm = tuple(child.dims.index(k) for k in op.keys) + tuple(
        child.dims.index(e) for e in elim
    )
    m = jnp.transpose(masked, perm)
    p = jnp.transpose(child.present, perm)
    ax = tuple(range(len(op.keys), len(child.dims)))
    strategy = ctx.connectors.get(
        ctx.label, "dense-reduce" if monoid.kernel_op else "segment-scan"
    )
    if not ax:
        red = m
    elif strategy == "dense-reduce" and monoid.kernel_op is not None:
        red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[
            monoid.kernel_op
        ](m, axis=ax)
    else:
        segments = int(np.prod([n] * len(op.keys), dtype=np.int64))
        rows = m.size // max(segments, 1)
        flat = m.reshape((-1,))
        ids = jnp.repeat(
            jnp.arange(segments, dtype=jnp.int32), rows
        )
        red = segment_combine_sorted(flat, ids, segments, op.agg).reshape(
            (n,) * len(op.keys)
        )
    pres = jnp.any(p, axis=ax) if ax else p
    return _Inter(tuple(op.keys), pres, {op.out_col: red})


# ---------------------------------------------------------------------------
# Signature inference (key vs value columns per predicate)
# ---------------------------------------------------------------------------


class _Unresolved(Exception):
    pass


def _op_types(
    op: algebra.LogicalOp,
    sigs: Mapping[str, Tuple[Tuple[int, ...], Tuple[int, ...]]],
    relations: Mapping[str, Relation],
) -> Dict[str, str]:
    """Column name -> ``"k"`` (vertex-domain grid dim) or ``"v"`` (value)."""

    if isinstance(op, algebra.ScanEDB):
        if op.relation == "__unit__":
            return {}
        rel = relations.get(op.relation)
        if rel is None:
            raise ExecutorError(f"missing EDB relation {op.relation!r}")
        if rel.arity != len(op.columns):
            raise ExecutorError(
                f"EDB {op.relation!r}: relation has arity {rel.arity}, "
                f"program uses {len(op.columns)}"
            )
        return {
            c: ("k" if i in rel.key_positions else "v")
            for i, c in enumerate(op.columns)
        }
    if isinstance(op, (algebra.ScanState, algebra.ScanView,
                       algebra.Frontier, algebra.Delta)):
        sig = sigs.get(op.relation)
        if sig is None:
            raise _Unresolved(op.relation)
        keys, _ = sig
        return {
            c: ("k" if i in keys else "v") for i, c in enumerate(op.columns)
        }
    if isinstance(op, (algebra.Join, algebra.Cross)):
        lt = _op_types(op.left, sigs, relations)
        rt = _op_types(op.right, sigs, relations)
        out = dict(rt)
        out.update(lt)
        for c in set(lt) & set(rt):
            if lt[c] == "k" or rt[c] == "k":
                out[c] = "k"
        return out
    if isinstance(op, algebra.AntiJoin):
        # the right side must still be resolvable (raises _Unresolved)
        _op_types(op.right, sigs, relations)
        return _op_types(op.left, sigs, relations)
    if isinstance(op, algebra.Select):
        return _op_types(op.child, sigs, relations)
    if isinstance(op, algebra.Project):
        t = _op_types(op.child, sigs, relations)
        return {c: t[c] for c in op.columns if c in t}
    if isinstance(op, algebra.Extend):
        t = _op_types(op.child, sigs, relations)
        t[op.column] = "v"
        return t
    if isinstance(op, algebra.Apply):
        t = _op_types(op.child, sigs, relations)
        for c in op.out_cols:
            t[c] = "v"
        return t
    if isinstance(op, algebra.GroupBy):
        t = _op_types(op.child, sigs, relations)
        out = {k: t.get(k, "k") for k in op.keys}
        out[op.out_col] = "v"
        return out
    if isinstance(op, algebra.Unnest):
        raise ExecutorError(
            "set-valued unnesting is a Listing-1 construct (use the "
            "VertexProgram binding)"
        )
    raise ExecutorError(f"unsupported logical operator {type(op).__name__}")


def _infer_signatures(
    dataflows: Sequence[algebra.RuleDataflow],
    relations: Mapping[str, Relation],
) -> Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    sigs: Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]] = {}
    pending = list(dataflows)
    while pending:
        progress, deferred = False, []
        for df in pending:
            try:
                t = _op_types(df.op, sigs, relations)
            except _Unresolved:
                deferred.append(df)
                continue
            schema = df.op.schema()
            keys = tuple(
                i for i, c in enumerate(schema) if t.get(c) == "k"
            )
            vals = tuple(
                i for i in range(len(schema)) if i not in keys
            )
            sig = (keys, vals)
            old = sigs.get(df.target)
            if old is not None and old != sig:
                raise ExecutorError(
                    f"predicate {df.target!r}: rules disagree on its "
                    f"key/value signature ({old} vs {sig})"
                )
            sigs[df.target] = sig
            progress = True
        if not progress:
            missing = sorted({
                err_pred
                for df in deferred
                for err_pred in _unresolved_preds(df.op, sigs, relations)
            })
            raise ExecutorError(
                "cannot infer key/value signatures for predicates "
                f"{missing} — every recursive predicate needs an "
                "initialization rule grounding it from the EDB"
            )
        pending = deferred
    return sigs


def _unresolved_preds(op, sigs, relations):
    try:
        _op_types(op, sigs, relations)
        return []
    except _Unresolved as err:
        return [err.args[0]]
    except ExecutorError:
        return []


# ---------------------------------------------------------------------------
# Generic executable: phase-sequenced fixpoints over the grid backend
# ---------------------------------------------------------------------------


@dataclass
class _Phase:
    index: int                      # 1-based phase number
    carried: Tuple[str, ...]        # recursive predicates updated here
    init: Tuple[algebra.RuleDataflow, ...]
    body: Tuple[algebra.RuleDataflow, ...]
    # View rules nothing in the body reads (e.g. a frontier view consumed
    # only by post-stratum rules): evaluated once at the fixpoint, not per
    # iteration.
    finals: Tuple[algebra.RuleDataflow, ...]
    post: Tuple[algebra.RuleDataflow, ...]


def _referenced_preds(op: algebra.LogicalOp) -> set:
    preds = set()
    if isinstance(op, (algebra.ScanEDB, algebra.ScanState, algebra.ScanView,
                       algebra.Frontier, algebra.Delta)):
        preds.add(op.relation)
    for child in op.children():
        preds |= _referenced_preds(child)
    return preds


@dataclass
class _ShiftedInjector:
    """Adapter making a :class:`~repro.ft.elastic.FailureInjector` count in
    *global* iterations across a multi-phase run (the driver hands it the
    phase-local index): crash-at-iteration-N then targets the same step the
    checkpoint numbering uses, so a chaos test can aim at a specific phase.
    """

    def __init__(self, inner: Any, base: int) -> None:
        self.inner, self.base = inner, base

    def maybe_fail(self, j: int) -> None:
        self.inner.maybe_fail(self.base + j)

    def maybe_fail_chunk(self, j: int, chunk: int) -> None:
        """Chunk-granular crash point of the out-of-core streaming loop
        (no-op for injectors without a chunk schedule)."""

        hook = getattr(self.inner, "maybe_fail_chunk", None)
        if hook is not None:
            hook(self.base + j, chunk)


@dataclass
class GenericExecutable:
    """A compiled generic program: logical plan + grid backend + drivers."""

    program: Program
    logical: algebra.LogicalPlan
    plan: Any                        # planner.ProgramPlan
    relations: Dict[str, Relation]
    sigs: Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]]
    phases: Tuple[_Phase, ...]
    prelude: Tuple[algebra.RuleDataflow, ...]
    domain: int
    mesh: Optional[Mesh]
    semi_naive: bool = False
    merge_monoids: Dict[str, Optional[str]] = field(default_factory=dict)
    # Canonical shared-subtree ids from the rewrite pass (CSE): _eval
    # memoizes these nodes once per evaluation context.
    shared_ids: FrozenSet[int] = frozenset()
    # Elastic fault tolerance: one note per remesh this executable's lineage
    # went through (propagated into FixpointResult.remesh_events), plus the
    # compile kwargs :meth:`remesh` needs to re-derive the physical plan.
    remesh_events: Tuple[str, ...] = ()
    _compile_kwargs: Dict[str, Any] = field(default_factory=dict, repr=False)
    # Physical storage per predicate ("dense-grid" / "row-table"), the
    # row-table slab capacities, the shared intermediate capacity, and the
    # precomputed row-table EDB slabs (planner storage selection).
    storage: Dict[str, str] = field(default_factory=dict)
    row_caps: Dict[str, int] = field(default_factory=dict)
    row_cap: int = 0
    row_edb: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    # Out-of-core streaming: per-predicate HOST-resident chunk lists (numpy
    # row slabs, all chunks of a predicate identically shaped) for EDB scans
    # whose working set exceeds the planner's HBM budget.  The fixpoint step
    # streams them through the device with double-buffered transfers,
    # accumulating per-chunk partials through the merge-monoid registry.
    chunked_edb: Dict[str, List[Dict[str, Any]]] = field(default_factory=dict)
    # Serving: memoized jitted per-phase steps.  Per-request inputs
    # (materialized views, parameter grids) are traced *arguments* of the
    # cached wrappers, so repeat dispatches against this executable — the
    # plan-cache hit path — reuse one XLA compilation instead of retracing
    # a fresh closure every run.
    _step_cache: Dict[Any, Callable] = field(
        default_factory=dict, repr=False, compare=False
    )

    # -- state plumbing -----------------------------------------------------

    @property
    def _any_row(self) -> bool:
        return any(s == "row-table" for s in self.storage.values())

    def _is_row(self, pred: str) -> bool:
        return self.storage.get(pred) == "row-table"

    def _empty_out(self, pred: str) -> Dict[str, Any]:
        keys, vals = self.sigs[pred]
        if self._is_row(pred):
            cap = self.row_caps[pred]
            return {
                "ids": jnp.zeros((cap, len(keys)), jnp.int32),
                "present": jnp.zeros((cap,), jnp.bool_),
                "values": {p: jnp.zeros((cap,), jnp.float32) for p in vals},
            }
        shape = (self.domain,) * len(keys)
        return {
            "present": jnp.zeros(shape, jnp.bool_),
            "values": {p: jnp.zeros(shape, jnp.float32) for p in vals},
        }

    def _empty_entry(self, pred: str) -> Dict[str, Any]:
        entry = self._empty_out(pred)
        entry["delta"] = jnp.zeros_like(entry["present"])
        if self._any_row:
            # Every carried entry gets the traced overflow leaf (ORed each
            # step) so capacity flags always have a home, even when this
            # particular predicate is dense in a mixed-storage plan.
            entry["overflow"] = jnp.asarray(False)
        return entry

    def _batch_axes(self) -> Tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(
            a for a in ("pod", "data") if self.mesh.shape.get(a, 1) > 1
        )

    def _placer(self):
        if self.mesh is None:
            return lambda a: a
        batch_axes = self._batch_axes()
        if not batch_axes:
            return lambda a: a
        n_shards = int(np.prod([self.mesh.shape[a] for a in batch_axes]))
        mesh, domain = self.mesh, self.domain

        def place(a):
            a = jnp.asarray(a)
            if a.ndim >= 1 and a.shape[0] == domain and domain % n_shards == 0:
                return jax.device_put(a, NamedSharding(mesh, P(batch_axes)))
            return jax.device_put(a, NamedSharding(mesh, P()))

        return place

    def _ctx(self, state, views, materialized, j, label="",
             relations=None) -> _Ctx:
        return _Ctx(
            program=self.program,
            n=self.domain,
            sigs=self.sigs,
            relations=self.relations if relations is None else relations,
            state=state,
            views=views,
            materialized=materialized,
            connectors=self.plan.connectors,
            j=j,
            label=label,
            shared=self.shared_ids,
            storage=self.storage,
            row_caps=self.row_caps,
            row_cap=self.row_cap,
            row_edb=self.row_edb,
            edb_join_rows=self._compile_kwargs.get("row_cap") is None,
            exchanges=dict(getattr(self.plan, "exchanges", {}) or {}),
            exchange_caps=dict(getattr(self.plan, "exchange_caps", {}) or {}),
            mesh=self.mesh,
            batch_axes=self._batch_axes(),
            chunked=frozenset(self.chunked_edb),
        )

    def _materialize(self, df, inter, ctx: _Ctx) -> Dict[str, Any]:
        """Lower a rule-body intermediate into the head predicate's storage
        (dense grid or row table), inserting the boundary converter when the
        body evaluated on the other representation.  Returns an *out* dict:
        ``{present, values}`` (dense) or ``{ids, present, values}`` (rows,
        ``present`` doubling as the slot validity mask)."""

        if self._is_row(df.target):
            rows = inter if isinstance(inter, _Rows) \
                else _inter_to_rows(inter, ctx)
            return self._materialize_rows(df, rows, ctx)
        if isinstance(inter, _Rows):
            inter = _rows_to_inter(inter, ctx)
        schema = df.op.schema()
        keys, vals = self.sigs[df.target]
        key_dims = tuple(schema[p] for p in keys)
        for d in key_dims:
            if d not in inter.dims:
                raise ExecutorError(
                    f"rule {df.label}: key column {d!r} of {df.target!r} is "
                    "not a grid dimension of the rule body"
                )
        perm = tuple(inter.dims.index(d) for d in key_dims)
        shape = (self.domain,) * len(key_dims)
        present = jnp.broadcast_to(
            jnp.transpose(inter.present, perm), shape
        )
        values = {}
        for p in vals:
            col = schema[p]
            if col not in inter.cols:
                raise ExecutorError(
                    f"rule {df.label}: value column {col!r} missing"
                )
            g = jnp.transpose(inter.cols[col], perm)
            values[p] = jnp.broadcast_to(g.astype(jnp.float32), shape)
        return {"present": present, "values": values}

    def _materialize_rows(self, df, rows: _Rows, ctx: _Ctx) -> Dict[str, Any]:
        schema = df.op.schema()
        keys, vals = self.sigs[df.target]
        key_dims = tuple(schema[p] for p in keys)
        for d in key_dims:
            if d not in rows.dims:
                raise ExecutorError(
                    f"rule {df.label}: key column {d!r} of {df.target!r} is "
                    "not a grid dimension of the rule body"
                )
        cap = rows.ids.shape[0]
        ids = jnp.stack(
            [rows.ids[:, rows.dims.index(d)] for d in key_dims], axis=-1
        ) if key_dims else jnp.zeros((cap, 0), jnp.int32)
        values = {}
        for p in vals:
            col = schema[p]
            if col not in rows.cols:
                raise ExecutorError(
                    f"rule {df.label}: value column {col!r} missing"
                )
            values[p] = jnp.broadcast_to(
                rows.cols[col], (cap,)
            ).astype(jnp.float32)
        return self._resize_rows(
            {"ids": ids, "present": rows.valid, "values": values},
            self.row_caps[df.target], ctx,
        )

    def _resize_rows(self, out, new_cap: int, ctx: _Ctx) -> Dict[str, Any]:
        """Re-slab a row out to the predicate's capacity: pad when growing,
        compact (overflow-flagged) when shrinking."""

        cap = out["ids"].shape[0]
        if cap == new_cap:
            return out
        if cap < new_cap:
            pad = new_cap - cap
            return {
                "ids": jnp.pad(out["ids"], ((0, pad), (0, 0))),
                "present": jnp.pad(out["present"], (0, pad)),
                "values": {
                    p: jnp.pad(v, (0, pad))
                    for p, v in out["values"].items()
                },
            }
        idx, valid = compact_active_edges(out["present"], new_cap)
        ctx.overflow.append(
            jnp.sum(out["present"].astype(jnp.int32)) > new_cap
        )
        take = jnp.minimum(idx, cap - 1)
        return {
            "ids": out["ids"][take],
            "present": valid,
            "values": {p: v[take] for p, v in out["values"].items()},
        }

    def _merge(self, pred: str, outs, ctx: _Ctx) -> Dict[str, Any]:
        if not outs:
            return self._empty_out(pred)
        if self._is_row(pred):
            return self._merge_rows(pred, outs, ctx)
        present = functools.reduce(
            jnp.logical_or, [o["present"] for o in outs]
        )
        _, vals = self.sigs[pred]
        if not vals:
            return {"present": present, "values": {}}
        agg = self.merge_monoids.get(pred)
        if agg is None:
            if len(outs) > 1:
                raise ExecutorError(
                    f"predicate {pred!r}: multiple rules derive value "
                    "columns without a combining head aggregate"
                )
            return {"present": present, "values": dict(outs[0]["values"])}
        monoid = _monoid_for(agg)
        ident = jnp.asarray(float(monoid.identity), jnp.float32)
        values = {}
        for p in vals:
            parts = [
                jnp.where(o["present"], o["values"][p], ident) for o in outs
            ]
            values[p] = functools.reduce(monoid.combine, parts)
        return {"present": present, "values": values}

    def _merge_rows(self, pred: str, outs, ctx: _Ctx) -> Dict[str, Any]:
        """Union-merge row outs: concatenate the slabs, dedupe by row code
        (representative-first), and fold duplicate values through the merge
        monoid — then re-slab to the predicate capacity."""

        if len(outs) == 1:
            return outs[0]
        _, vals = self.sigs[pred]
        agg = self.merge_monoids.get(pred)
        if vals and agg is None:
            raise ExecutorError(
                f"predicate {pred!r}: multiple rules derive value "
                "columns without a combining head aggregate"
            )
        ids = jnp.concatenate([o["ids"] for o in outs], axis=0)
        valid = jnp.concatenate([o["present"] for o in outs], axis=0)
        cat_vals = {
            p: jnp.concatenate([o["values"][p] for o in outs], axis=0)
            for p in vals
        }
        cap = ids.shape[0]
        try:
            codes = row_codes(ids, self.domain)
        except ValueError as err:
            raise ExecutorError(str(err)) from err
        perm, skey, n_valid = sort_row_codes(codes, valid)
        is_new, seg = unique_row_runs(skey, n_valid)
        in_valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
        values = {}
        if vals:
            monoid = _monoid_for(agg)
            for p in vals:
                red = segment_combine_sorted(
                    cat_vals[p][perm], seg, cap, agg, edge_active=in_valid
                )
                values[p] = red[seg]
        merged = {"ids": ids[perm], "present": is_new, "values": values}
        return self._resize_rows(merged, self.row_caps[pred], ctx)

    @staticmethod
    def _diff(old, present, values):
        diff = old["present"] != present
        both = jnp.logical_and(old["present"], present)
        for p, v in values.items():
            diff = jnp.logical_or(
                diff, jnp.logical_and(both, old["values"][p] != v)
            )
        return diff

    def _diff_rows(self, old, new):
        """Row-diff: ``(delta_mask_over_new, changed_scalar)`` — a new row
        is delta when its key tuple is absent from the old table or any
        value column changed; ``changed`` additionally catches rows that
        disappeared (the presence-count check)."""

        try:
            old_codes = row_codes(old["ids"], self.domain)
            new_codes = row_codes(new["ids"], self.domain)
        except ValueError as err:
            raise ExecutorError(str(err)) from err
        operm, oskey, onv = sort_row_codes(old_codes, old["present"])
        cap_o = oskey.shape[0]
        pos = jnp.searchsorted(oskey, new_codes, side="left").astype(jnp.int32)
        posc = jnp.minimum(pos, cap_o - 1)
        member = jnp.logical_and(pos < onv, oskey[posc] == new_codes)
        changed_val = jnp.zeros_like(member)
        for p, v in new["values"].items():
            old_v = old["values"][p][operm][posc]
            changed_val = jnp.logical_or(changed_val, old_v != v)
        delta = jnp.logical_and(
            new["present"],
            jnp.logical_or(~member, jnp.logical_and(member, changed_val)),
        )
        shrunk = jnp.sum(old["present"].astype(jnp.int32)) != jnp.sum(
            new["present"].astype(jnp.int32)
        )
        changed = jnp.logical_or(jnp.any(delta), shrunk)
        return delta, changed

    def _rows_to_relation(self, pred: str, entry) -> RowRelation:
        """Host-side: pack a row entry into a lex-sorted RowRelation (the
        same tuple order :meth:`Relation.tuples` produces)."""

        keys, vals = self.sigs[pred]
        ids = np.asarray(entry["ids"])
        present = np.asarray(entry["present"])
        rows = ids[present].astype(np.int32)
        order = np.lexsort(rows.T[::-1]) if rows.shape[0] else \
            np.arange(0, dtype=np.int64)
        return RowRelation(
            n=self.domain,
            key_positions=keys,
            rows=rows[order],
            values={
                p: np.asarray(entry["values"][p])[present][order]
                for p in vals
            },
        )

    # -- per-phase step -----------------------------------------------------

    def _apply_body(self, phase: _Phase, ctx: _Ctx, state, dataflows, acc,
                    of_extra):
        """Fire a phase's body dataflows and seal the carried entries.
        ``acc`` pre-seeds per-target out lists (the chunked streaming loop
        passes its accumulated partials) and ``of_extra`` folds overflow
        flags raised outside this trace (per-chunk firings) into the
        carried overflow leaves."""

        views = ctx.views
        for df in dataflows:
            ctx.label = df.label
            ctx.exchange_target = df.target
            out = self._materialize(df, _eval(df.op, ctx), ctx)
            if df.next_state:
                acc.setdefault(df.target, []).append(out)
            else:
                if df.target in views:
                    views[df.target] = self._merge(
                        df.target, [views[df.target], out], ctx
                    )
                else:
                    views[df.target] = out
        new_state = dict(state)
        for pred in phase.carried:
            out = self._merge(pred, acc.get(pred, []), ctx)
            if self._is_row(pred):
                delta, _ = self._diff_rows(state[pred], out)
            else:
                delta = jnp.logical_and(
                    out["present"],
                    self._diff(state[pred], out["present"], out["values"]),
                )
            entry = dict(out)
            entry["delta"] = delta
            if self._any_row:
                # Fold every capacity flag this step raised (including
                # the merges above) into the carried overflow leaf.
                step_of = functools.reduce(
                    jnp.logical_or, ctx.overflow, of_extra
                )
                entry["overflow"] = jnp.logical_or(
                    state[pred].get("overflow", False), step_of
                )
            new_state[pred] = entry
        return new_state

    def _phase_step(self, phase: _Phase, materialized,
                    relations=None) -> Callable:
        def step(state, j):
            views: Dict[str, Dict[str, Any]] = {}
            ctx = self._ctx(state, views, materialized, j,
                            relations=relations)
            return self._apply_body(
                phase, ctx, state, phase.body, {}, jnp.asarray(False)
            )

        return step

    def _phase_converged(self, phase: _Phase) -> Callable:
        def conv(prev, new):
            same = jnp.asarray(True)
            for pred in phase.carried:
                if self._is_row(pred):
                    _, changed = self._diff_rows(prev[pred], new[pred])
                    same = jnp.logical_and(same, ~changed)
                else:
                    diff = self._diff(
                        prev[pred], new[pred]["present"], new[pred]["values"]
                    )
                    same = jnp.logical_and(same, ~jnp.any(diff))
            return same

        return conv

    def _raise_on_overflow(self, ctx: _Ctx) -> None:
        """Host-side eager overflow check (prelude/init/final rule groups
        run untraced, so their flags are checked immediately)."""

        if ctx.overflow and bool(
            functools.reduce(jnp.logical_or, ctx.overflow)
        ):
            raise _RowCapacityOverflow()

    def _run_rules_once(self, dataflows, state, materialized, j,
                        relations=None):
        """Fire a rule group once (init / final-view / post rules), merging
        multi-rule targets, and return {target: entry}."""

        acc: Dict[str, list] = {}
        order: List[str] = []
        views: Dict[str, Dict[str, Any]] = {}
        ctx = self._ctx(state, views, materialized, j, relations=relations)
        base_edb = ctx.row_edb
        for df in dataflows:
            ctx.label = df.label
            ctx.exchange_target = df.target
            refs = self._chunk_refs(df)
            if refs:
                # Out-of-core scan in a once-fired rule group: stream the
                # chunks eagerly and fold the partials through the merge
                # monoid (chunk-count-invariant by monoid associativity).
                pred = refs[0]
                outs = []
                for chunk in self.chunked_edb[pred]:
                    ctx.row_edb = dict(base_edb)
                    ctx.row_edb[pred] = self._put_chunk(chunk)
                    outs.append(
                        self._materialize(df, _eval(df.op, ctx), ctx)
                    )
                ctx.row_edb = base_edb
                out = self._merge(df.target, outs, ctx) \
                    if len(outs) > 1 else outs[0]
            else:
                out = self._materialize(df, _eval(df.op, ctx), ctx)
            if df.target not in acc:
                order.append(df.target)
            acc.setdefault(df.target, []).append(out)
            # make the target readable by later rules in this group
            views[df.target] = self._merge(df.target, acc[df.target], ctx)
        self._raise_on_overflow(ctx)
        return {t: views[t] for t in order}

    # -- out-of-core chunked streaming (host-resident EDB slabs) ------------

    def _chunk_refs(self, df) -> Tuple[str, ...]:
        """The chunked EDB predicates a dataflow's body scans (compile-time
        validation guarantees at most one)."""

        if not self.chunked_edb:
            return ()
        return tuple(sorted(
            _referenced_preds(df.op) & set(self.chunked_edb)
        ))

    def _put_chunk(self, chunk) -> Dict[str, Any]:
        """Device-place one host chunk as a row-EDB overlay table."""

        place = self._placer()
        return {
            "ids": place(jnp.asarray(chunk["ids"])),
            "valid": place(jnp.asarray(chunk["valid"])),
            "values": {
                p: place(jnp.asarray(v))
                for p, v in chunk["values"].items()
            },
        }

    def _chunk_fire_fn(self, phase: _Phase, pred: str, dfs) -> Callable:
        """Memoized jitted firing of the body rules scanning one chunked
        predicate: evaluates them against a chunk overlay and folds the
        outs into the running per-target accumulators through the merge
        monoids — ``fire(state, acc, materialized, params, overlay, j)``."""

        key = ("chunk-fire", phase.index, pred)
        fn = self._step_cache.get(key)
        if fn is None:
            def fire(state, acc, materialized, params, overlay, j,
                     _dfs=dfs, _pred=pred):
                rels = self._bind_params(params)
                ctx = self._ctx(state, {}, materialized, j, relations=rels)
                ctx.row_edb = dict(self.row_edb)
                ctx.row_edb[_pred] = overlay
                # Chunk-proportional intermediates: the planner's join /
                # convert cap carries 4x headroom over the largest slab,
                # and a firing that scans 1/m of the chunked slab expects
                # ~1/m of the join pairs — so the per-chunk intermediate
                # keeps the same headroom at 1/m the sort/gather cost.
                # Skew beyond it trips the usual lossless overflow path.
                m = len(self.chunked_edb[_pred])
                if ctx.row_cap and m > 1:
                    per = -(-ctx.row_cap // m)
                    ctx.row_cap = max(
                        256, 1 << max(per - 1, 0).bit_length()
                    )
                out_acc = dict(acc)
                for df in _dfs:
                    ctx.label = df.label
                    ctx.exchange_target = df.target
                    out = self._materialize(df, _eval(df.op, ctx), ctx)
                    out_acc[df.target] = self._merge(
                        df.target, [out_acc[df.target], out], ctx
                    )
                of = functools.reduce(
                    jnp.logical_or, ctx.overflow, jnp.asarray(False)
                )
                return out_acc, of

            fn = jit_hoisted(fire)
            self._step_cache[key] = fn
        return fn

    def _chunk_finish_fn(self, phase: _Phase, plain_dfs,
                         chunk_targets) -> Callable:
        """Memoized jitted tail of a chunked phase step: fires the
        non-chunked body rules and seals the carried entries, seeding the
        per-target accumulators with the streamed partials (and folding the
        chunk loop's overflow flags into the carried leaves)."""

        key = ("chunk-finish", phase.index)
        fn = self._step_cache.get(key)
        if fn is None:
            def finish(state, acc, of_chunks, materialized, params, j,
                       _dfs=plain_dfs, _targets=chunk_targets):
                rels = self._bind_params(params)
                views: Dict[str, Dict[str, Any]] = {}
                ctx = self._ctx(state, views, materialized, j,
                                relations=rels)
                accs = {t: [acc[t]] for t in _targets}
                return self._apply_body(
                    phase, ctx, state, _dfs, accs, of_chunks
                )

            fn = jit_hoisted(finish)
            self._step_cache[key] = fn
        return fn

    def _chunked_phase_step(self, phase: _Phase, materialized, param_grids,
                            injector=None) -> Callable:
        """The host-driven per-iteration step of a phase whose body scans
        chunked (out-of-core) EDB predicates: for each such predicate the
        host streams its chunk list through the jitted ``fire`` stage with
        double-buffered async host-to-device transfers (the next chunk's
        ``device_put`` is issued before the current one is consumed), then
        the jitted ``finish`` stage fires the remaining rules and seals the
        carried state.  Partial accumulators live only inside one step
        invocation, so a mid-chunk crash (``injector.maybe_fail_chunk``)
        discards them and the driver's restore+replay recomputes the step
        from checkpointed state — chunk cursors never need checkpointing.
        """

        chunk_dfs: Dict[str, List] = {}
        for df in phase.body:
            refs = self._chunk_refs(df)
            if refs:
                chunk_dfs.setdefault(refs[0], []).append(df)
        plain = tuple(df for df in phase.body if not self._chunk_refs(df))
        targets = tuple(dict.fromkeys(
            df.target for dfs in chunk_dfs.values() for df in dfs
        ))
        place = self._placer()
        fire_fns = {
            pred: self._chunk_fire_fn(phase, pred, tuple(dfs))
            for pred, dfs in chunk_dfs.items()
        }
        finish = self._chunk_finish_fn(phase, plain, targets)

        def step(state, jj):
            j = jnp.int32(jj)
            acc = {
                t: jax.tree_util.tree_map(place, self._empty_out(t))
                for t in targets
            }
            of = jnp.asarray(False)
            for pred, fire in fire_fns.items():
                chunks = self.chunked_edb[pred]
                cur = self._put_chunk(chunks[0])
                for c in range(len(chunks)):
                    # double buffering: enqueue the next H2D transfer
                    # before dispatching compute on the current chunk
                    nxt = self._put_chunk(chunks[c + 1]) \
                        if c + 1 < len(chunks) else None
                    if injector is not None:
                        injector.maybe_fail_chunk(jj, c)
                    acc, ov = fire(state, acc, materialized, param_grids,
                                   cur, j)
                    of = jnp.logical_or(of, ov)
                    cur = nxt
            return finish(state, acc, of, materialized, param_grids, j)

        return step

    # -- parameterized query bindings (online serving) ----------------------

    def _param_grids(self, params) -> Dict[str, Dict[str, Any]]:
        """Validate a per-query parameter binding ``{name: Relation}`` and
        lower it to raw grid leaves (the traced arguments of the memoized
        step wrappers).  Fail closed: a parameter may only rebind a dense
        EDB relation of the compiled program, on the same signature."""

        grids: Dict[str, Dict[str, Any]] = {}
        for name, rel in (params or {}).items():
            base = self.relations.get(name)
            if base is None:
                raise ExecutorError(
                    f"parameter {name!r} is not an EDB relation of the "
                    "compiled program"
                )
            if (isinstance(base, RowRelation) or isinstance(rel, RowRelation)
                    or name in self.row_edb or self._is_row(name)):
                raise ExecutorError(
                    f"parameter {name!r} is row-table-stored; parameterized "
                    "bindings need dense-grid storage (fail closed)"
                )
            if rel.n != self.domain:
                raise ExecutorError(
                    f"parameter {name!r}: domain {rel.n} != compiled "
                    f"domain {self.domain}"
                )
            if (tuple(rel.key_positions) != tuple(base.key_positions)
                    or set(rel.values) != set(base.values)):
                raise ExecutorError(
                    f"parameter {name!r} does not match the compiled "
                    "relation signature (key/value positions differ)"
                )
            grids[name] = {
                "present": jnp.asarray(rel.present),
                "values": {p: jnp.asarray(g) for p, g in rel.values.items()},
            }
        return grids

    def _bind_params(self, grids) -> Optional[Dict[str, Relation]]:
        """An EDB view with the parameter grids swapped in (shared graph
        relations stay the device-resident compile-time grids)."""

        if not grids:
            return None
        rels = dict(self.relations)
        for name, entry in grids.items():
            base = self.relations[name]
            rels[name] = Relation(
                n=self.domain,
                key_positions=base.key_positions,
                present=entry["present"],
                values=dict(entry["values"]),
            )
        return rels

    def _jitted_step(self, phase: _Phase, batched: bool = False) -> Callable:
        """The memoized jitted step of one fixpoint phase, as
        ``step(state, materialized, param_grids, j)``.  Everything that
        changes between requests is an argument; loop-invariant EDB grids
        are closed over, and :class:`~repro.core.fixpoint.jit_hoisted`
        passes them to the compiled step as device-resident arguments.
        ``batched=True`` vmaps the step over a leading query axis of (state,
        materialized, params) with ``j`` broadcast — one fixpoint serving k
        queries."""

        key = ("batched" if batched else "seq", phase.index)
        fn = self._step_cache.get(key)
        if fn is None:
            def step_one(state, materialized, params, j, _phase=phase):
                rels = self._bind_params(params)
                return self._phase_step(
                    _phase, materialized, relations=rels
                )(state, j)

            fn = jit_hoisted(
                jax.vmap(step_one, in_axes=(0, 0, 0, None))
                if batched else step_one
            )
            self._step_cache[key] = fn
        return fn

    def _batched_fn(self, kind: str, phase: Optional[_Phase] = None):
        """Memoized jitted+vmapped non-step stages of a batched run —
        prelude, per-phase init, per-phase finals — so plan-cache-hit
        dispatches pay none of the eager-vmap interpretation cost
        ``run_batched`` would otherwise spend outside the fixpoint loop."""

        key = (kind, None if phase is None else phase.index)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn

        if kind == "prelude":
            def one(params):
                rels = self._bind_params(params)
                state = {
                    pred: self._empty_entry(pred)
                    for ph in self.phases for pred in ph.carried
                }
                mat: Dict[str, Dict[str, Any]] = {}
                mat.update(self._run_rules_once(
                    self.prelude, state, mat, jnp.int32(0), relations=rels
                ))
                return state, mat

            fn = jit_hoisted(jax.vmap(one))
        elif kind == "init":
            def one(state, mat, params, _phase=phase):
                rels = self._bind_params(params)
                inits = self._run_rules_once(
                    _phase.init, state, mat, jnp.int32(0), relations=rels
                )
                out = dict(state)
                for pred in _phase.carried:
                    entry = inits.get(pred)
                    if entry is not None:
                        out[pred] = self._init_entry(entry)
                return out

            fn = jit_hoisted(jax.vmap(one))
        elif kind == "finals":
            def one(state, mat, params, j, _phase=phase):
                rels = self._bind_params(params)
                m = dict(mat)
                m.update(self._run_rules_once(
                    tuple(df for df in _phase.body if not df.next_state)
                    + _phase.finals,
                    state, m, j, relations=rels,
                ))
                m.update(self._run_rules_once(
                    _phase.post, state, m, j, relations=rels
                ))
                return m

            fn = jit_hoisted(jax.vmap(one, in_axes=(0, 0, 0, None)))
        elif kind == "conv":
            conv_one = self._phase_converged(phase)

            def one(prev, new, _c=conv_one):
                return jnp.all(jax.vmap(_c)(prev, new))

            fn = jit_hoisted(one)
        else:
            raise ExecutorError(f"unknown batched stage {kind!r}")
        self._step_cache[key] = fn
        return fn

    def run_batched(
        self,
        param_sets,
        max_iters: int,
        on_device: bool = False,
    ) -> List[FixpointResult]:
        """Run k parameterized queries through ONE shared fixpoint.

        ``param_sets`` is a sequence of per-query bindings
        ``{name: Relation}`` (every set must bind the same parameter
        relations).  The per-phase step is vmapped over a leading query
        axis; a phase iterates until *every* query's no-new-facts test
        holds (extra iterations are no-ops for already-converged queries —
        a converged state is a fixed point of the step).  Answers are
        bit-comparable to k sequential ``run(..., params=...)`` calls.

        Fail closed: batching needs all-dense storage (row-table slabs
        carry host-checked overflow flags that cannot cross a vmap
        boundary) — admission policies route such plans to sequential
        dispatch (see ``repro.core.planner.serving_admission``).
        """

        if not param_sets:
            raise ExecutorError("run_batched needs at least one param set")
        if self._any_row or self.row_edb or self.chunked_edb:
            raise ExecutorError(
                "query batching needs all-dense storage: row-table slabs "
                "carry capacity-overflow flags the vmapped fixpoint cannot "
                "check host-side, and chunked EDB streams need the host "
                "chunk loop (fail closed; dispatch sequentially)"
            )
        grids = [self._param_grids(ps) for ps in param_sets]
        names = set(grids[0])
        if any(set(g) != names for g in grids[1:]):
            raise ExecutorError(
                "every batched param set must bind the same relations"
            )
        if not names:
            raise ExecutorError(
                "run_batched needs parameterized bindings (identical "
                "queries batch trivially — dispatch one run instead)"
            )
        k = len(grids)
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *grids
        )

        t0 = time.perf_counter()
        state_b, mat_b = self._batched_fn("prelude")(stacked)

        total = 0
        phase_iters: List[int] = []
        all_conv = True
        for phase in self.phases:
            state_b = self._batched_fn("init", phase)(
                state_b, mat_b, stacked
            )
            bstep = self._jitted_step(phase, batched=True)
            bconv = self._batched_fn("conv", phase)

            if on_device:
                res = device_fixpoint(
                    lambda s, j, _b=bstep: _b(s, mat_b, stacked, j),
                    bconv, state_b, max_iters,
                )
            else:
                driver = HostFixpointDriver(
                    step=lambda s, jj, _b=bstep: _b(
                        s, mat_b, stacked, jnp.int32(jj)
                    ),
                    converged=bconv,
                    config=DriverConfig(max_iters=max_iters),
                )
                res = driver.run(state_b)
            state_b = res.state
            total += res.iterations
            phase_iters.append(res.iterations)
            all_conv = all_conv and res.converged

            mat_b = self._batched_fn("finals", phase)(
                state_b, mat_b, stacked, jnp.int32(res.iterations)
            )

        seconds = time.perf_counter() - t0
        entries = list(mat_b.items()) + [
            (p, state_b[p]) for ph in self.phases for p in ph.carried
        ]
        results: List[FixpointResult] = []
        for q in range(k):
            out: Dict[str, Any] = {}
            for pred, entry in entries:
                keys, _ = self.sigs[pred]
                out[pred] = Relation(
                    n=self.domain,
                    key_positions=keys,
                    present=entry["present"][q],
                    values={p: v[q] for p, v in entry["values"].items()},
                )
            results.append(FixpointResult(
                state=out,
                iterations=total,
                converged=all_conv,
                seconds=seconds,
                phase_iterations=tuple(phase_iters),
                remesh_events=self.remesh_events,
            ))
        return results

    def phase_step_fn(self) -> Tuple[Callable, Dict[str, Dict[str, Any]]]:
        """Benchmark hook: the jitted per-iteration step of the FIRST
        fixpoint phase plus its initialized state — times exactly one rule
        firing of the recursive stratum, the unit the drivers repeat."""

        if any(self._chunk_refs(df) for df in self.phases[0].body):
            raise ExecutorError(
                "phase_step_fn cannot time a chunked phase: the out-of-core "
                "chunk stream is a host loop, not one jitted step"
            )
        place = self._placer()
        state: Dict[str, Dict[str, Any]] = {}
        for phase in self.phases:
            for pred in phase.carried:
                state[pred] = jax.tree_util.tree_map(
                    place, self._empty_entry(pred)
                )
        materialized = dict(self._run_rules_once(
            self.prelude, state, {}, jnp.int32(0)
        ))
        phase = self.phases[0]
        inits = self._run_rules_once(
            phase.init, state, materialized, jnp.int32(0)
        )
        for pred in phase.carried:
            entry = inits.get(pred)
            if entry is not None:
                state[pred] = jax.tree_util.tree_map(
                    place, self._init_entry(entry)
                )
        return jit_hoisted(self._phase_step(phase, materialized)), state

    def compiled_steps(self) -> List[Any]:
        """The compiled programs of every jitted step this executable has
        run (``jax.stages.Compiled``): what ran on the device."""

        return [c for fn in self._step_cache.values() for c in fn.compiled()]

    def _init_entry(self, out: Dict[str, Any]) -> Dict[str, Any]:
        """Promote a materialized out into a carried entry: everything is
        new at J=0, so the delta mask starts as the presence mask."""

        entry = dict(out)
        entry["delta"] = out["present"]
        if self._any_row:
            entry["overflow"] = jnp.asarray(False)
        return entry

    # -- durable checkpoints (fault tolerance) ------------------------------

    def _mat_targets(self) -> Tuple[str, ...]:
        """Every predicate the run materializes outside the carried state,
        in a deterministic order — the checkpoint's ``mat`` leaves.  The set
        is a pure function of the compiled program, so the checkpoint tree
        structure is constant across phases (targets a resumed run has not
        reached yet are stored as zero grids and recomputed)."""

        order: List[str] = []
        groups = [self.prelude] + [
            tuple(df for df in ph.body if not df.next_state)
            + ph.finals + ph.post
            for ph in self.phases
        ]
        for group in groups:
            for df in group:
                if df.target not in order:
                    order.append(df.target)
        return tuple(order)

    def _zeros_view(self, pred: str) -> Dict[str, Any]:
        return self._empty_out(pred)

    def _ckpt_tree(self, state, materialized) -> Dict[str, Any]:
        """The durable snapshot of an in-flight run: all carried state plus
        every materialized view (zero-padded for targets not yet computed).
        Leaves are written host-side/unsharded by the store, so a checkpoint
        taken on one mesh restores onto any other (elastic remesh)."""

        mat = {
            t: (
                dict(e, values=dict(e["values"]))
                if (e := materialized.get(t)) is not None
                else self._zeros_view(t)
            )
            for t in self._mat_targets()
        }
        return {"state": {p: dict(e) for p, e in state.items()},
                "mat": mat}

    def _ckpt_like(self) -> Dict[str, Any]:
        """Host-side zero template matching :meth:`_ckpt_tree`'s structure
        (the ``like`` argument of :func:`repro.checkpoint.restore_pytree`)."""

        state = {
            pred: self._empty_entry(pred)
            for ph in self.phases for pred in ph.carried
        }
        return self._ckpt_tree(state, {})

    def remesh(self, mesh: Optional[Mesh]) -> "GenericExecutable":
        """Recompile this program onto a new (typically shrunken) mesh after
        device loss: the physical plan is re-derived for the surviving
        topology (``plan_program`` re-invoked), the EDB grids are re-placed,
        and the remesh is recorded in ``plan.notes`` and carried into
        ``FixpointResult.remesh_events``.  Host-side checkpoints written by
        the old executable restore directly into the new one."""

        old_n = 1 if self.mesh is None else int(self.mesh.devices.size)
        new = compile_program(
            self.program, self.relations, mesh=mesh,
            semi_naive=self.semi_naive, domain=self.domain,
            **self._compile_kwargs,
        )
        if mesh is None:
            shape, new_n = "1 device", 1
        else:
            shape = "x".join(
                f"{n}={s}" for n, s in zip(mesh.axis_names, mesh.devices.shape)
            )
            new_n = int(mesh.devices.size)
        note = f"remesh({old_n}->{new_n}: {shape})"
        new.plan = replace(new.plan, notes=new.plan.notes + (note,))
        new.remesh_events = self.remesh_events + (note,)
        return new

    # -- fixpoint entry point ----------------------------------------------

    def run(
        self,
        max_iters: int,
        on_device: bool = False,
        *,
        params: Optional[Mapping[str, Relation]] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        injector: Optional[Any] = None,
        max_restarts: int = 3,
        keep_checkpoints: int = 3,
    ) -> FixpointResult:
        """Run every fixpoint phase in sequence to the no-new-facts
        fixpoint (``max_iters`` bounds each phase).

        ``params`` rebinds dense EDB relations for THIS run only (online
        serving: per-query seed/source/target bindings).  The swapped
        grids ride the memoized jitted steps as traced arguments, so a
        cached plan dispatches new parameter values without recompiling.

        Fault tolerance (host driver only): ``checkpoint_dir`` plugs a
        :class:`~repro.checkpoint.CheckpointStore` into the driver's
        save/restore hooks — carried state + materialized views are written
        host-side every ``checkpoint_every`` iterations (default 8) along
        with the phase cursor, so a crashed run restarts mid-phase and a
        ``resume=True`` run continues from disk without re-running completed
        phases.  ``injector`` threads a
        :class:`~repro.ft.elastic.FailureInjector` into the step boundary.

        Returns a :class:`FixpointResult` whose ``state`` maps every
        materialized predicate to its final :class:`Relation` (or
        :class:`RowRelation` for row-table-stored predicates).

        Overflow policy (lossless): when any row-table slab overflows its
        static capacity mid-run, the run is abandoned and transparently
        re-executed on dense-grid storage (``storage_fallback=True`` on the
        result).  The fallback run does not checkpoint — its tree structure
        differs from the row run's — so overflow-prone programs that need
        durability should pre-size ``row_cap=`` or force dense storage.
        """

        try:
            return self._run_phases(
                max_iters, on_device, params=params,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                injector=injector, max_restarts=max_restarts,
                keep_checkpoints=keep_checkpoints,
            )
        except _RowCapacityOverflow:
            return self._dense_fallback_run(max_iters, on_device, params)

    def _dense_fallback_run(
        self, max_iters: int, on_device: bool,
        params: Optional[Mapping[str, Relation]] = None,
    ) -> FixpointResult:
        for name, rel in self.relations.items():
            if isinstance(rel, RowRelation):
                raise ExecutorError(
                    f"row-table capacity overflow, and EDB {name!r} is a "
                    "RowRelation whose dense grid is infeasible — raise "
                    "compile_program(row_cap=) instead"
                )
        kwargs = {
            k: v for k, v in self._compile_kwargs.items()
            if k not in ("storage", "row_cap", "chunks")
        }
        dense = compile_program(
            self.program, self.relations, mesh=self.mesh,
            semi_naive=self.semi_naive, domain=self.domain,
            storage="dense-grid", **kwargs,
        )
        # Result metadata survives the rerun: the fallback executable is
        # this one's lineage, so remesh events accumulated before the
        # overflow trip stay on the final FixpointResult.
        dense.remesh_events = self.remesh_events
        res = dense.run(max_iters, on_device, params=params)
        return replace(res, storage_fallback=True)

    def _run_phases(
        self,
        max_iters: int,
        on_device: bool = False,
        *,
        params: Optional[Mapping[str, Relation]] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        injector: Optional[Any] = None,
        max_restarts: int = 3,
        keep_checkpoints: int = 3,
    ) -> FixpointResult:
        param_grids = self._param_grids(params)
        prels = self._bind_params(param_grids)
        if (checkpoint_dir or injector) and on_device:
            raise ExecutorError(
                "fault tolerance (checkpoint_dir/injector) needs the host "
                "driver: pass on_device=False"
            )
        if resume and not checkpoint_dir:
            raise ExecutorError("resume=True needs checkpoint_dir=")
        store = None
        if checkpoint_dir is not None:
            from repro.checkpoint import CheckpointStore, latest_step

            store = CheckpointStore(checkpoint_dir, keep=keep_checkpoints)
            if checkpoint_every <= 0:
                checkpoint_every = 8

        t0 = time.perf_counter()
        place = self._placer()
        state: Dict[str, Dict[str, Any]] = {}
        for phase in self.phases:
            for pred in phase.carried:
                state[pred] = jax.tree_util.tree_map(
                    place, self._empty_entry(pred)
                )
        materialized: Dict[str, Dict[str, Any]] = {}
        for out, entry in self._run_rules_once(
            self.prelude, state, materialized, jnp.int32(0), relations=prels
        ).items():
            materialized[out] = entry

        # Resume cursor: phase to continue in (1-based), iteration within it
        # (checkpoints are written post-init, so a restored state never needs
        # the init stratum re-fired), and completed phases' iteration counts.
        start_phase, start_iter = 1, 0
        done_iters: List[int] = []
        restored_from_disk = False
        if store is not None and resume and \
                latest_step(checkpoint_dir) is not None:
            restored_from_disk = True
            tree, _, extra = store.restore(self._ckpt_like())
            tree = jax.tree_util.tree_map(place, tree)
            state = tree["state"]
            start_phase = int(extra.get("phase", 1))
            start_iter = int(extra.get("iteration", 0))
            done_iters = [int(x) for x in extra.get("phase_iterations", [])]
            # Materialized views of completed phases come from the
            # checkpoint (their fixpoints are sealed); the current and later
            # phases recompute theirs.
            for ph in self.phases[: start_phase - 1]:
                for df in (
                    tuple(d for d in ph.body if not d.next_state)
                    + ph.finals + ph.post
                ):
                    materialized[df.target] = tree["mat"][df.target]

        total = sum(done_iters)
        phase_iters, all_conv = list(done_iters), True
        restarts_total = stragglers_total = 0
        for phase in self.phases:
            k = phase.index
            if k < start_phase:
                continue
            resumed = restored_from_disk and k == start_phase
            if not resumed:
                inits = self._run_rules_once(
                    phase.init, state, materialized, jnp.int32(0),
                    relations=prels,
                )
                for pred in phase.carried:
                    entry = inits.get(pred)
                    if entry is None:
                        continue
                    state[pred] = jax.tree_util.tree_map(
                        place, self._init_entry(entry)
                    )
            chunked_phase = any(self._chunk_refs(df) for df in phase.body)
            step = self._phase_step(phase, materialized, relations=prels)
            conv = self._phase_converged(phase)
            if on_device:
                if chunked_phase:
                    raise ExecutorError(
                        "chunked streaming needs the host driver: the chunk "
                        "loop issues host-to-device transfers inside every "
                        "iteration (pass on_device=False)"
                    )
                res = device_fixpoint(step, conv, state, max_iters)
            else:
                shifted = None if injector is None \
                    else _ShiftedInjector(injector, total)
                if chunked_phase:
                    step_req = self._chunked_phase_step(
                        phase, materialized, param_grids, injector=shifted
                    )
                else:
                    jitted = self._jitted_step(phase)

                    def step_req(s, jj, _jit=jitted):
                        return _jit(
                            s, materialized, param_grids, jnp.int32(jj)
                        )
                save_hook = restore_hook = None
                if store is not None:
                    base = total  # global step counter offset for this phase
                    completed = list(phase_iters)

                    def save_hook(s, jj, _k=k, _b=base, _c=completed):
                        # "chunk" is the out-of-core stream cursor: chunk
                        # partials live only inside one step invocation
                        # (never checkpointed), so a restored step always
                        # replays its chunk stream from 0.
                        store.save(
                            _b + jj, self._ckpt_tree(s, materialized),
                            extra={"phase": _k, "iteration": jj,
                                   "phase_iterations": _c, "chunk": 0},
                        )

                    def restore_hook(_k=k):
                        tr, _, ex = store.restore(self._ckpt_like())
                        if int(ex.get("phase", -1)) != _k:
                            raise RuntimeError(
                                f"latest checkpoint belongs to phase "
                                f"{ex.get('phase')}; cannot rewind into "
                                f"phase {_k} mid-driver"
                            )
                        return (
                            jax.tree_util.tree_map(place, tr["state"]),
                            int(ex.get("iteration", 0)),
                        )

                    # Phase-entry restore point (post-init, iteration 0):
                    # guarantees the current phase always has a checkpoint
                    # a mid-phase crash can rewind to.
                    if not resumed:
                        save_hook(state, 0)
                driver = HostFixpointDriver(
                    step=step_req,
                    converged=conv,
                    config=DriverConfig(
                        max_iters=max_iters,
                        checkpoint_every=checkpoint_every if store else 0,
                        max_restarts=max_restarts,
                    ),
                    save=save_hook,
                    restore=restore_hook,
                    injector=shifted,
                )
                try:
                    res = driver.run(
                        state, start_iter=start_iter if resumed else 0
                    )
                except BaseException:
                    # The failure is already propagating: drain the async
                    # writer so it cannot race a successor run (or resume)
                    # over the same checkpoint directory.
                    if store is not None:
                        store.quiesce()
                    raise
                restarts_total += res.restarts
                stragglers_total += res.straggler_events
            state = res.state
            # Lossless overflow policy: any capacity flag raised inside the
            # (jitted) fixpoint surfaces here, before the phase's results
            # are consumed.
            for pred in phase.carried:
                of = state[pred].get("overflow")
                if of is not None and bool(of):
                    raise _RowCapacityOverflow()
            it = (start_iter if resumed else 0) + res.iterations
            total += res.iterations
            phase_iters.append(it)
            all_conv = all_conv and res.converged
            # Final views of this phase (frontier reads at the fixpoint),
            # then the post-stratum rules gated on its convergence.
            finals = self._run_rules_once(
                tuple(df for df in phase.body if not df.next_state)
                + phase.finals,
                state, materialized, jnp.int32(it), relations=prels,
            )
            materialized.update(finals)
            posts = self._run_rules_once(
                phase.post, state, materialized, jnp.int32(it),
                relations=prels,
            )
            materialized.update(posts)
        if store is not None:
            store.wait()  # surface any pending async-save failure

        out: Dict[str, Any] = {}
        for pred, entry in list(materialized.items()) + [
            (p, state[p]) for ph in self.phases for p in ph.carried
        ]:
            keys, _ = self.sigs[pred]
            if self._is_row(pred):
                out[pred] = self._rows_to_relation(pred, entry)
            else:
                out[pred] = Relation(
                    n=self.domain,
                    key_positions=keys,
                    present=entry["present"],
                    values=dict(entry["values"]),
                )
        return FixpointResult(
            state=out,
            iterations=total,
            converged=all_conv,
            seconds=time.perf_counter() - t0,
            restarts=restarts_total,
            phase_iterations=tuple(phase_iters),
            straggler_events=stragglers_total,
            remesh_events=self.remesh_events,
        )


# ---------------------------------------------------------------------------
# compile_program — the unified entry point
# ---------------------------------------------------------------------------


def _listing_shape(program: Program) -> Optional[str]:
    labels = tuple(r.label for r in program.rules)
    if program.name == "pregel" and labels == (
        "L1", "L2", "L3", "L4", "L5", "L6", "L7", "L8"
    ):
        return "pregel"
    if program.name == "imru" and labels == ("G1", "G2", "G3"):
        return "imru"
    return None


def compile_program(
    program: Program,
    relations: Mapping[str, Any],
    *,
    mesh: Optional[Mesh] = None,
    binding: Any = None,
    semi_naive: bool = False,
    domain: Optional[int] = None,
    hw: HardwareSpec = TPU_V5E,
    force_connector: Optional[str] = None,
    rewrite: bool = False,
    storage: Any = None,
    row_cap: Optional[int] = None,
    exchange: Any = None,
    hbm_budget: Optional[int] = None,
    chunks: Any = None,
    **frontend_kwargs,
):
    """Compile ANY XY-stratified program onto the unified executor.

    ``relations`` binds the EDB: for generic programs, dense-grid
    :class:`Relation` instances (or raw int tuple arrays with ``domain=``);
    for the paper's listings, the front-end physical inputs (Listing 1:
    ``{"data": Graph}``; Listing 2: ``{"training_data": records}``).

    ``binding`` supplies the vectorized UDF bundle for the listing fast
    paths — a :class:`~repro.core.pregel.VertexProgram` or
    :class:`~repro.core.imru.IMRUTask`.  When the program matches a listing
    shape, the planner selects the specialized pipeline (semi-naive sparse
    supersteps, fused exchanges, reduce trees) as the operator
    implementation; everything else runs on the generic dense-grid
    interpreter with sequential fixpoint phases.

    ``rewrite=True`` runs the :mod:`repro.core.rewrite` optimizer pass
    (join reordering, select pushdown, cross-rule CSE) over the logical
    plan before physical planning; the decisions are recorded in
    ``plan.notes`` as a ``rewrite(...)`` entry.  Listing fast paths ignore
    the flag (their plans are already specialized), keeping their plan
    notes byte-identical with and without it.

    ``storage=`` overrides the planner's per-predicate physical storage
    selection: a string (``"dense-grid"`` / ``"row-table"``) forces every
    predicate, a mapping forces individual predicates (the rest stay
    cost-selected).  Predicates bound to a :class:`RowRelation` EDB are
    always row-table (their dense grid is infeasible).  ``row_cap=`` pins
    the row-table intermediate slab capacity.  The selection is recorded in
    ``plan.notes`` as the ``storage-selection(...)`` entry.

    ``exchange=`` overrides the planner's explicit-exchange connector
    selection for row-table GroupBy/Join sites on data-parallel meshes: a
    string (``"bucket-a2a"`` / ``"psum-scatter"`` / ``"gspmd"``) forces
    every row predicate, a mapping forces individual head predicates.  The
    selection is recorded per predicate as ``exchange(<pred>: ...)`` notes.

    ``hbm_budget=`` (bytes) overrides the per-device working-set budget the
    planner chunks out-of-core EDB scans against (default: half the
    hardware spec's HBM); ``chunks=`` forces chunk counts (an int for every
    row-table EDB, or a per-predicate mapping).  Chunked predicates keep
    their slabs host-resident and stream through the fixpoint step in
    planner-chosen chunk counts (``chunking(<pred>: ...)`` notes),
    accumulating per-chunk partials through the merge-monoid registry so
    results are chunk-count-invariant.
    """

    shape = _listing_shape(program)
    if shape == "pregel" and binding is not None:
        from repro.core.pregel import compile_pregel

        return compile_pregel(
            binding, relations["data"], mesh=mesh, semi_naive=semi_naive,
            force_connector=force_connector, hw=hw, **frontend_kwargs,
        )
    if shape == "imru" and binding is not None:
        from repro.core.imru import compile_imru

        return compile_imru(
            binding, relations["training_data"], mesh=mesh, hw=hw,
            **frontend_kwargs,
        )
    if shape is not None:
        raise ExecutorError(
            f"Listing program {program.name!r} needs its vectorized "
            "front-end binding (binding=VertexProgram(...) or "
            "binding=IMRUTask(...)): its set-valued message slabs have no "
            "dense-grid encoding"
        )

    program.validate()
    schedule = stratify.iteration_schedule(program)
    logical = algebra.translate(program)
    sn_notes: Tuple[str, ...] = ()
    if semi_naive:
        logical, sn_notes = algebra.semi_naive_rewrite(logical, program)

    # Normalize + cache the EDB grids (loop-invariant, device-resident).
    rels: Dict[str, Relation] = {}
    for name, value in relations.items():
        rels[name] = _as_relation(name, value, domain)
    if domain is None:
        domains = {r.n for r in rels.values()}
        if len(domains) != 1:
            raise ExecutorError(
                "pass domain= (EDB relations disagree on the vertex domain)"
            )
        domain = domains.pop()
    for name in program.edb:
        if name not in rels:
            raise ExecutorError(f"missing EDB relation {name!r}")

    # Rewrite-rule optimizer pass (join reorder, select pushdown, CSE) —
    # runs on the logical DAG before signatures/phases/planning so the
    # rewritten operator trees are what the interpreter executes.
    rw_notes: Tuple[str, ...] = ()
    shared_ids: FrozenSet[int] = frozenset()
    if rewrite:
        from repro.core.rewrite import rewrite_plan

        rewritten = rewrite_plan(logical, program, rels, domain)
        logical = rewritten.plan
        rw_notes = rewritten.notes
        shared_ids = rewritten.shared_ids

    sigs = _infer_signatures(
        tuple(logical.init) + tuple(logical.body), rels
    )

    # Sequential fixpoint phases: recursive SCCs in topological order; every
    # other rule is scheduled around them by the deepest phase it reads.
    phase_groups = stratify.fixpoint_phases(program)
    pred_phase: Dict[str, int] = {}
    for i, group in enumerate(phase_groups):
        for p in group:
            pred_phase[p] = i + 1
    changed = True
    while changed:
        changed = False
        for rule in program.rules:
            head = rule.head.pred
            if any(head in g for g in phase_groups):
                continue  # recursive predicates keep their SCC phase
            dep = 0
            for lit in rule.body:
                atom = getattr(lit, "atom", lit)
                pred = getattr(atom, "pred", None)
                if pred is not None:
                    dep = max(dep, pred_phase.get(pred, 0))
            if pred_phase.get(head, -1) < dep:
                pred_phase[head] = dep
                changed = True

    init_dfs = list(logical.init)
    body_dfs = list(logical.body)
    carried_set = set(schedule.carried)

    prelude: List[algebra.RuleDataflow] = []
    phase_init: Dict[int, List] = {}
    phase_body: Dict[int, List] = {}
    phase_post: Dict[int, List] = {}
    # translate() emits one dataflow per schedule rule, in order — zip
    # positionally (labels may repeat or be empty).
    for df, rule in zip(init_dfs, schedule.init_rules):
        dep = 0
        for lit in rule.body:
            atom = getattr(lit, "atom", lit)
            pred = getattr(atom, "pred", None)
            if pred is not None:
                dep = max(dep, pred_phase.get(pred, 0))
        if df.target in carried_set:
            k = pred_phase[df.target]
            if dep >= k:
                raise ExecutorError(
                    f"rule {df.label}: initialization of phase-{k} "
                    f"predicate {df.target!r} reads a phase-{dep} result"
                )
            phase_init.setdefault(k, []).append(df)
        elif dep == 0:
            prelude.append(df)
        else:
            phase_post.setdefault(dep, []).append(df)
    for df in body_dfs:
        k = pred_phase.get(df.target)
        if k is None or k == 0:
            raise ExecutorError(
                f"per-iteration rule {df.label} targets non-recursive "
                f"predicate {df.target!r}"
            )
        phase_body.setdefault(k, []).append(df)

    phases: List[_Phase] = []
    for i, group in enumerate(phase_groups):
        k = i + 1
        body = list(phase_body.get(k, ()))
        # Views nothing in this phase's body reads run once at the
        # fixpoint instead of every iteration (e.g. P4's rankF frontier
        # view, consumed only by the post-stratum threshold rule).
        reads = set()
        for df in body:
            reads |= _referenced_preds(df.op)
        kept = tuple(
            df for df in body if df.next_state or df.target in reads
        )
        finals = tuple(
            df for df in body
            if not df.next_state and df.target not in reads
        )
        phases.append(_Phase(
            index=k,
            carried=tuple(sorted(group)),
            init=tuple(phase_init.get(k, ())),
            body=kept,
            finals=finals,
            post=tuple(phase_post.get(k, ())),
        ))

    # Merge monoids: the combining aggregate for targets derived by
    # several rules (union semantics resolved through the monoid registry).
    merge_monoids: Dict[str, Optional[str]] = {}
    for rule in program.rules:
        aggs = rule.head_aggregates()
        if not aggs:
            continue
        name = aggs[0].agg
        prev = merge_monoids.get(rule.head.pred)
        if prev is not None and prev != name:
            raise ExecutorError(
                f"predicate {rule.head.pred!r} is aggregated with both "
                f"{prev!r} and {name!r}"
            )
        merge_monoids[rule.head.pred] = name

    # GroupBy sites for the planner's connector selection.
    specs: List[GroupBySpec] = []
    for df in init_dfs + body_dfs:
        specs.extend(_collect_groupbys(df, sigs, rels, domain))

    if mesh is not None:
        mesh_spec = MeshSpec(tuple(
            (nm, s) for nm, s in zip(mesh.axis_names, mesh.devices.shape)
        ))
    else:
        mesh_spec = MeshSpec((("data", 1),))

    # Storage selection inputs: (key arity, estimated row count) for every
    # predicate — EDB counts are exact, derived predicates come from the
    # optimizer's iterated cardinality model.
    from repro.core.rewrite import estimate_program_cardinalities

    ests = estimate_program_cardinalities(
        tuple(logical.init) + tuple(logical.body), rels, domain
    )
    predicates: Dict[str, Tuple[int, float]] = {}
    for name, rel in rels.items():
        predicates[name] = (len(rel.key_positions), float(rel.count()))
    for pred, (keys_pos, _) in sigs.items():
        predicates[pred] = (
            len(keys_pos), float(ests.get(pred, float(domain) ** len(keys_pos)))
        )
    forced: Dict[str, str] = {}
    if isinstance(storage, str):
        forced = {p: storage for p in predicates}
    elif storage:
        forced = dict(storage)
    for name, rel in rels.items():
        if isinstance(rel, RowRelation):
            if forced.get(name, "row-table") != "row-table":
                raise ExecutorError(
                    f"EDB {name!r} is a RowRelation: its dense grid is "
                    "infeasible, storage cannot be forced to dense-grid"
                )
            forced[name] = "row-table"

    # Explicit-exchange selection inputs: the merge monoid's kernel op per
    # head predicate decides psum-scatter admission; chunking applies to
    # row-table EDB scans sized by their key arity + value-column count.
    exchange_ops: Dict[str, Optional[str]] = {}
    for pred, agg in merge_monoids.items():
        if agg is not None:
            try:
                exchange_ops[pred] = get_monoid(agg).kernel_op
            except MonoidError:
                exchange_ops[pred] = None

    plan = plan_program(
        tuple(tuple(sorted(g)) for g in phase_groups),
        tuple(specs), domain, mesh_spec, hw,
        semi_naive=semi_naive, extra_notes=sn_notes + rw_notes,
        predicates=predicates, storage=forced or None, row_cap=row_cap,
        exchange=exchange, exchange_ops=exchange_ops,
        hbm_budget=hbm_budget, chunks=chunks,
        edb=tuple(sorted(rels)),
        row_value_cols={
            name: len(rel.values) for name, rel in rels.items()
        },
    )

    ex = GenericExecutable(
        program=program,
        logical=logical,
        plan=plan,
        relations=rels,
        sigs=sigs,
        phases=tuple(phases),
        prelude=tuple(prelude),
        domain=domain,
        mesh=mesh,
        semi_naive=semi_naive,
        merge_monoids=merge_monoids,
        shared_ids=shared_ids,
        _compile_kwargs={"hw": hw, "force_connector": force_connector,
                         "rewrite": rewrite, "storage": storage,
                         "row_cap": row_cap, "exchange": exchange,
                         "hbm_budget": hbm_budget, "chunks": chunks},
        storage=dict(plan.storage),
        row_caps=dict(plan.row_caps),
        row_cap=plan.row_cap,
    )
    # Device-place copies of the EDB grids (loop-invariant caching) — the
    # caller's Relation objects stay untouched, so one Relation can feed
    # compiles on different meshes.  RowRelations stay host-side numpy (the
    # placed slabs below are what the interpreter reads).
    place = ex._placer()
    ex.relations = {
        name: (
            rel if isinstance(rel, RowRelation) else Relation(
                n=rel.n,
                key_positions=rel.key_positions,
                present=place(rel.present),
                values={p: place(g) for p, g in rel.values.items()},
            )
        )
        for name, rel in rels.items()
    }
    # Row-table EDB slabs (loop-invariant caching, sparse storage): compact
    # the tuples host-side once, pad to the planned capacity, device-place.
    for name, rel in rels.items():
        if plan.storage.get(name) != "row-table":
            continue
        cap = plan.row_caps[name]
        k = len(rel.key_positions)
        if isinstance(rel, RowRelation):
            rows = rel.rows
            raw_vals = {p: np.asarray(v) for p, v in rel.values.items()}
        else:
            rows = np.argwhere(np.asarray(rel.present)).astype(np.int32)
            raw_vals = {
                p: np.asarray(g)[tuple(rows.T)]
                for p, g in rel.values.items()
            }
        count = rows.shape[0]
        m = int(getattr(plan, "chunks", {}).get(name, 0))
        if m > 1:
            # Out-of-core streaming: split the slab into m identically
            # shaped HOST-resident chunks (numpy) — the fixpoint step
            # streams them through HBM instead of device-placing the
            # whole slab.
            per = max(-(-count // m), 1)
            ccap = 1 << max(per - 1, 0).bit_length()
            chunk_list: List[Dict[str, Any]] = []
            for c in range(m):
                sl = rows[c * per:(c + 1) * per]
                cnt = sl.shape[0]
                ids_c = np.zeros((ccap, k), np.int32)
                ids_c[:cnt] = sl
                valid_c = np.zeros((ccap,), bool)
                valid_c[:cnt] = True
                vals_c = {}
                for p, v in raw_vals.items():
                    col = np.zeros((ccap,), np.float32)
                    col[:cnt] = v[c * per:(c + 1) * per].astype(np.float32)
                    vals_c[p] = col
                chunk_list.append(
                    {"ids": ids_c, "valid": valid_c, "values": vals_c}
                )
            ex.chunked_edb[name] = chunk_list
            continue
        if count > cap:
            raise ExecutorError(
                f"EDB {name!r}: {count} rows exceed its row-table "
                f"capacity {cap}"
            )
        ids = np.zeros((cap, k), np.int32)
        ids[:count] = rows
        valid = np.zeros((cap,), bool)
        valid[:count] = True
        values = {}
        for p, v in raw_vals.items():
            col = np.zeros((cap,), np.float32)
            col[:count] = v.astype(np.float32)
            values[p] = place(jnp.asarray(col))
        ex.row_edb[name] = {
            "ids": place(jnp.asarray(ids)),
            "valid": place(jnp.asarray(valid)),
            "values": values,
        }
    if ex.chunked_edb:
        _check_chunk_soundness(ex)
    return ex


def _check_chunk_soundness(ex: GenericExecutable) -> None:
    """Fail-closed validation that streaming a predicate's chunks through
    the fixpoint is chunk-count-invariant: a rule scanning a chunked EDB
    fires once per chunk and its partial outs fold through the
    CombineMonoid registry, which is only sound when the rule decomposes
    over a disjoint union of those scan rows."""

    chunked = set(ex.chunked_edb)
    body_views = {
        ph.index: {df.target for df in ph.body if not df.next_state}
        for ph in ex.phases
    }

    def check_df(df, phase: Optional[_Phase] = None,
                 is_body: bool = False) -> None:
        refs = _referenced_preds(df.op) & chunked
        if not refs:
            return
        if len(refs) > 1:
            raise ExecutorError(
                f"rule {df.label}: scans {len(refs)} chunked EDBs "
                f"({', '.join(sorted(refs))}) — the streaming loop "
                "decomposes one chunked scan per rule (fail closed)"
            )
        pred = next(iter(refs))
        if is_body and not df.next_state:
            raise ExecutorError(
                f"rule {df.label}: per-iteration view rule scans chunked "
                f"EDB {pred!r} — only carried-state rules stream through "
                "the chunk loop (fail closed)"
            )
        if is_body and phase is not None:
            read_views = _referenced_preds(df.op) & body_views[phase.index]
            if read_views:
                raise ExecutorError(
                    f"rule {df.label}: chunked rule reads same-phase view "
                    f"{sorted(read_views)[0]!r}, which the streaming loop "
                    "fires after the chunk partials (fail closed)"
                )

        def no_anti(op) -> None:
            if isinstance(op, algebra.AntiJoin) and (
                _referenced_preds(op.right) & chunked
            ):
                raise ExecutorError(
                    f"rule {df.label}: chunked EDB {pred!r} on the negated "
                    "side of an AntiJoin — set difference against a "
                    "partial chunk is not chunk-invariant (fail closed)"
                )
            for child in op.children():
                no_anti(child)

        def check_gb(op, root: bool) -> None:
            if isinstance(op, algebra.GroupBy) and (
                _referenced_preds(op) & chunked
            ):
                if not root or ex.merge_monoids.get(df.target) != op.agg:
                    raise ExecutorError(
                        f"rule {df.label}: aggregation over chunked EDB "
                        f"{pred!r} must be the rule's head aggregate (its "
                        "per-chunk partials fold through the head monoid; "
                        "fail closed)"
                    )
            for child in op.children():
                check_gb(child, False)

        no_anti(df.op)
        check_gb(df.op, True)
        _, vals = ex.sigs[df.target]
        if vals and ex.merge_monoids.get(df.target) is None:
            raise ExecutorError(
                f"rule {df.label}: target {df.target!r} carries value "
                f"columns but no merge monoid — per-chunk partials from "
                f"chunked EDB {pred!r} cannot combine (fail closed)"
            )

    for df in ex.prelude:
        check_df(df)
    for ph in ex.phases:
        for df in ph.init + ph.finals + ph.post:
            check_df(df, phase=ph)
        for df in ph.body:
            check_df(df, phase=ph, is_body=True)


def _collect_groupbys(df, sigs, relations, domain) -> List[GroupBySpec]:
    found: List[GroupBySpec] = []

    def walk(op):
        for child in op.children():
            walk(child)
        if isinstance(op, algebra.GroupBy):
            try:
                t = _op_types(op.child, sigs, relations)
            except (_Unresolved, ExecutorError):
                return
            n_dims = sum(1 for v in t.values() if v == "k")
            monoid = _monoid_for(op.agg)
            found.append(GroupBySpec(
                label=df.label,
                agg=op.agg,
                rows=int(domain ** n_dims),
                segments=int(domain ** len(op.keys)),
                kernel_op=monoid.kernel_op,
            ))

    walk(df.op)
    return found


# ---------------------------------------------------------------------------
# Listing fast paths: the shared physical step builders
# ---------------------------------------------------------------------------
#
# The machinery below is what ``compile_pregel`` / ``compile_imru`` lower
# through — the shard_map partitioning, exchanges, and fixpoint steps that
# used to be duplicated inside the two front-ends.  The front-ends keep their
# public API and statistics probing; the executor owns the operators.

_EXCHANGES = {
    "dense_psum": dense_psum_exchange,
    "merging": merging_exchange,
    "hash_sort": hash_sort_exchange,
}

# Frontier-compacted connector variants (dense_psum has no sparse variant:
# its masked path keeps the N-sized psum but runs edge work on the slab).
_SPARSE_EXCHANGES = {
    "merging": sparse_merging_exchange,
    "hash_sort": sparse_hash_sort_exchange,
}


def _compact_and_gather(prog, j, state, active, src, dst,
                        cap: int, *, pad=None, edge_data=None):
    """Shared sparse-superstep prologue: mask the edge slab by source
    activity (and padding, on sharded slabs), compact the frontier into
    ``cap`` slots, gather the compacted endpoints/state/edge-data, and run
    the message UDF.  Returns ``(dst_c, payload, valid)`` for the exchange.
    Empty slots carry a clamped in-range index (their payload is computed
    from real state but excluded everywhere via ``valid``)."""

    if src.shape[0] == 0:
        # Zero-edge slab (an edgeless graph, or a mesh with more shards than
        # edges): the clamp below would wrap ``src.shape[0] - 1`` to -1 and
        # silently gather the *last* edge.  Synthesize one inert padding
        # edge instead so every downstream gather has a real row; it is
        # masked off via ``pad``, so the slab compacts to all-invalid slots
        # and the exchange drops everything it produces.
        src = jnp.zeros((1,), jnp.int32)
        dst = jnp.zeros((1,), jnp.int32)
        pad = jnp.ones((1,), jnp.bool_)
        edge_data = jax.tree_util.tree_map(
            lambda e: jnp.zeros((1,) + e.shape[1:], e.dtype), edge_data
        )
    mask = jnp.take(active, src, axis=0)
    if pad is not None:
        mask = jnp.logical_and(mask, jnp.logical_not(pad))
    idx, valid = compact_active_edges(mask, cap)
    idx_c = jnp.minimum(idx, src.shape[0] - 1)
    src_c = jnp.take(src, idx_c)
    dst_c = jnp.take(dst, idx_c)
    edata_c = (
        None if edge_data is None else jax.tree_util.tree_map(
            lambda e: jnp.take(e, idx_c, axis=0), edge_data
        )
    )
    src_state = jax.tree_util.tree_map(
        lambda s: jnp.take(s, src_c, axis=0), state
    )
    payload = prog.message(j, src_state, edata_c)
    return dst_c, payload, valid


def _apply_and_merge(prog, j, state, inbox, got):
    """Shared superstep epilogue (O8..O10 + L7): run the apply UDF, keep the
    old state wherever no message arrived, and halt those vertices.  Every
    superstep variant — dense/sparse, single-shard/sharded — must share this
    exact merge semantics or the execution strategies diverge.

    Monoids with a ``finalize`` (mean: (sum, count) -> sum/count) have it
    applied to the combined inbox HERE — the one seam every superstep
    variant shares — so the apply UDF always sees finalized values no
    matter which execution strategy produced the accumulator."""

    monoid = get_monoid(prog.combine)
    if monoid.finalize is not None:
        inbox = monoid.finalize(inbox)
    new_state, new_active = prog.apply(j, state, inbox, got)
    merged = jax.tree_util.tree_map(
        lambda old, new: jnp.where(
            got.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
        ),
        state, new_state,
    )
    return merged, jnp.logical_and(new_active, got)


@dataclass
class PregelStepBundle:
    """The executable steps ``compile_pregel`` wraps: the dense superstep,
    the frontier-compacted sparse factory (per static capacity), the
    shard-local count reduction, and the per-shard edge-slab size."""

    superstep: Callable
    sparse_step_factory: Callable[[int], Callable]
    shard_count_fn: Optional[Callable]
    local_edge_cap: int
    # Failure injection threaded from the compile call: the executable hands
    # this to its host driver, which fires ``maybe_fail(j)`` at the step
    # boundary — the same boundary where a real pod's runtime surfaces a
    # device failure (as an XLA error on the next dispatch).
    injector: Optional[Any] = None


def build_pregel_steps(prog, graph, plan, mesh,
                       injector=None) -> PregelStepBundle:
    """Materialize the planned Listing-1 superstep pipeline (Fig. 4).

    One code path builds both layouts: single-shard (trivial axes) and SPMD
    ``shard_map`` with per-shard edge slabs, the planned connector exchange,
    and the frontier-compacted sparse variants the adaptive driver swaps in.

    ``injector`` rides along on the bundle: failures cannot fire *inside*
    the jitted step functions (host side effects are traced out), so the
    chaos knob lives at the host step boundary between dispatches of the
    sharded steps built here.
    """

    connector = _EXCHANGES[plan.connector]
    op = prog.combine

    batch_axes = tuple(
        a for a in ("pod", "data")
        if mesh is not None and mesh.shape.get(a, 1) > 1
    )

    def local_superstep(state_shard, active_shard, src_l, dst_l,
                        edata_l, vdata_l, base, j):
        """One superstep on a shard (Fig. 4's O7..O15 pipeline).

        ``src_l`` holds *local* source indices (edges partitioned by owner
        of the source vertex); ``dst_l`` holds global destination ids.
        """

        # O7 index join: probe source state by gather (B-tree probe).
        src_state = jax.tree_util.tree_map(
            lambda s: jnp.take(s, src_l, axis=0), state_shard
        )
        src_active = jnp.take(active_shard, src_l, axis=0)
        payload = prog.message(j, src_state, edata_l)
        # Vote-to-halt: inactive sources contribute the combine identity
        # (a per-column identity row for structured monoids like argmin).
        payload = jnp.where(
            src_active.reshape((-1,) + (1,) * (payload.ndim - 1)),
            payload,
            get_monoid(op).identity_like(payload),
        )
        # O15 sender combine + connector + O14 receiver combine.
        inbox = connector(dst_l, payload, graph.n_vertices, batch_axes, op)
        got_msg = connector(
            dst_l,
            jnp.where(src_active, 1.0, 0.0),
            graph.n_vertices, batch_axes, "sum",
        ) > 0
        # O8 apply + O9/O10 masked in-place state update (non-null check L7):
        # vertices with no inbound messages keep their state and stay halted.
        return _apply_and_merge(prog, j, state_shard, inbox, got_msg)

    if mesh is not None and batch_axes:
        from jax.experimental.shard_map import shard_map

        n_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))
        if graph.n_vertices % n_shards:
            raise ValueError("n_vertices must divide the data shards")
        n_local = graph.n_vertices // n_shards

        # Partition edges by source-owner shard with equal (padded) counts.
        owner = np.asarray(graph.src) // n_local
        order = np.argsort(owner, kind="stable")
        counts = np.bincount(owner, minlength=n_shards)
        slab_cap = int(counts.max())
        src_p = np.full((n_shards, slab_cap), 0, np.int32)
        dst_p = np.full((n_shards, slab_cap), -1, np.int32)  # -1 = padding
        src_sorted = np.asarray(graph.src)[order]
        dst_sorted = np.asarray(graph.dst)[order]
        offs = np.zeros(n_shards + 1, np.int64)
        np.cumsum(counts, out=offs[1:])
        for s in range(n_shards):
            lo, hi = offs[s], offs[s + 1]
            src_p[s, : hi - lo] = src_sorted[lo:hi] - s * n_local
            dst_p[s, : hi - lo] = dst_sorted[lo:hi]
        # Padding edges: local source 0, destination = sentinel spill row; we
        # mark them inactive by pointing dst at vertex 0 with identity payload
        # (their source-active mask is forced off via dst -1 -> clamp).
        pad_mask = dst_p < 0
        dst_p = np.where(pad_mask, 0, dst_p)

        spec1 = P(batch_axes)
        # Each shard's edge slab lives on its own device from the start.
        src_arr, dst_arr, pad_arr = (
            jax.device_put(a.reshape(-1), NamedSharding(mesh, spec1))
            for a in (src_p, dst_p, pad_mask)
        )

        vdata = jax.device_put(
            graph.vertex_data, NamedSharding(mesh, spec1)
        )

        # Edge-slab partitioning of per-edge attributes: every edge_data
        # leaf rides the same owner permutation + padding as src/dst, so
        # slab row i always carries the attributes of the edge in slab row
        # i.  Padding rows are zero-filled — they are masked off (pad_mask)
        # before any payload they produce can travel.
        def _edge_slab(leaf):
            leaf_np = np.asarray(leaf)
            slab = np.zeros(
                (n_shards, slab_cap) + leaf_np.shape[1:], leaf_np.dtype
            )
            leaf_sorted = leaf_np[order]
            for s in range(n_shards):
                lo, hi = offs[s], offs[s + 1]
                slab[s, : hi - lo] = leaf_sorted[lo:hi]
            return jnp.asarray(
                slab.reshape((n_shards * slab_cap,) + leaf_np.shape[1:])
            )

        edata = None
        if graph.edge_data is not None:
            edata = jax.tree_util.tree_map(_edge_slab, graph.edge_data)
            edata = jax.device_put(edata, NamedSharding(mesh, spec1))
        espec = jax.tree_util.tree_map(lambda _: spec1, edata)

        def sharded(state, active, src_l, dst_l, pad_l, edata_l, vdata_l, j):
            # Mask padded edges: treat their source as inactive.
            act = jnp.logical_and(
                jnp.take(active, src_l, axis=0), jnp.logical_not(pad_l)
            )
            src_state = jax.tree_util.tree_map(
                lambda s: jnp.take(s, src_l, axis=0), state
            )
            payload = prog.message(j, src_state, edata_l)
            payload = jnp.where(
                act.reshape((-1,) + (1,) * (payload.ndim - 1)),
                payload,
                get_monoid(op).identity_like(payload),
            )
            dst_eff = jnp.where(pad_l, -1, dst_l)
            inbox = connector(
                jnp.where(dst_eff < 0, 0, dst_eff),
                payload, graph.n_vertices, batch_axes, op,
            )
            got = connector(
                jnp.where(dst_eff < 0, 0, dst_eff),
                jnp.where(act, 1.0, 0.0),
                graph.n_vertices, batch_axes, "sum",
            ) > 0
            return _apply_and_merge(prog, j, state, inbox, got)

        state_specs = P(batch_axes)
        fn = shard_map(
            sharded, mesh=mesh,
            in_specs=(state_specs, state_specs, spec1, spec1, spec1, espec,
                      jax.tree_util.tree_map(lambda _: spec1, vdata), P()),
            out_specs=(state_specs, state_specs),
            check_rep=False,
        )

        def superstep(carry, j):
            state, active = carry
            return fn(state, active, src_arr, dst_arr, pad_arr, edata,
                      vdata, j)

        # -- sharded semi-naive (delta-frontier) machinery ------------------

        def _local_count(active, src_l, pad_l):
            mask = jnp.logical_and(
                jnp.take(active, src_l, axis=0), jnp.logical_not(pad_l)
            )
            return jnp.sum(mask.astype(jnp.int32)).reshape(1)

        count_fn = jax.jit(shard_map(
            _local_count, mesh=mesh,
            in_specs=(state_specs, spec1, spec1),
            out_specs=P(batch_axes),
            check_rep=False,
        ))

        def shard_count_fn(active):
            return count_fn(active, src_arr, pad_arr)

        sparse_ex = _SPARSE_EXCHANGES.get(plan.connector)

        def sparse_step_factory(compact_cap: int) -> Callable:
            """Frontier-compacted sharded superstep: every shard compacts
            its local edge slab into the same static ``compact_cap`` slots
            (the host driver derives the capacity from the max shard-local
            count, keeping the mesh in SPMD lockstep), then all
            edge-proportional work — gather, message UDF, combine, and the
            cross-shard exchange payloads — scales with the frontier
            instead of the slab."""

            def step_shard(state, active, src_l, dst_l, pad_l, edata_l, j):
                dst_c, payload, valid = _compact_and_gather(
                    prog, j, state, active, src_l, dst_l, compact_cap,
                    pad=pad_l, edge_data=edata_l,
                )
                if sparse_ex is None:
                    # No sparse connector variant: the frontier-masked dense
                    # exchange still moves N-sized partials, but all
                    # edge-side work runs on the compacted slab.
                    ex = lambda fused: dense_psum_exchange(
                        dst_c, fused, graph.n_vertices, batch_axes, op,
                        edge_mask=valid, flag_cols=1,
                    )
                else:
                    ex = lambda fused: sparse_ex(
                        dst_c, fused, valid, graph.n_vertices, batch_axes,
                        op, flag_cols=1,
                    )
                inbox, got = fused_got_exchange(ex, payload, valid, op)
                return _apply_and_merge(prog, j, state, inbox, got)

            wrapped = shard_map(
                step_shard, mesh=mesh,
                in_specs=(state_specs, state_specs, spec1, spec1, spec1,
                          espec, P()),
                out_specs=(state_specs, state_specs),
                check_rep=False,
            )

            def step(carry, j):
                state, active = carry
                return wrapped(state, active, src_arr, dst_arr, pad_arr,
                               edata, j)

            return jit_hoisted(step)
    else:
        def superstep(carry, j):
            state, active = carry
            return local_superstep(
                state, active, graph.src, graph.dst, graph.edge_data,
                graph.vertex_data, 0, j,
            )

        sparse_ex = _SPARSE_EXCHANGES.get(plan.connector)

        def sparse_step_factory(cap: int) -> Callable:
            """Single-shard frontier-compacted superstep: all
            edge-proportional work (gather, message UDF, combine, exchange)
            runs over a ``cap``-sized compacted slab of the active edges
            instead of all E edges."""

            def step(carry, j):
                state, active = carry
                dst_c, payload, valid = _compact_and_gather(
                    prog, j, state, active, graph.src, graph.dst, cap,
                    edge_data=graph.edge_data,
                )
                if sparse_ex is None:
                    ex = lambda fused: dense_psum_exchange(
                        dst_c, fused, graph.n_vertices, (), op,
                        edge_mask=valid, flag_cols=1,
                    )
                else:
                    ex = lambda fused: sparse_ex(
                        dst_c, fused, valid, graph.n_vertices, (), op,
                        flag_cols=1,
                    )
                inbox, got = fused_got_exchange(ex, payload, valid, op)
                return _apply_and_merge(prog, j, state, inbox, got)

            return jit_hoisted(step)

        shard_count_fn = None
        slab_cap = graph.n_edges

    return PregelStepBundle(
        superstep=superstep,
        sparse_step_factory=sparse_step_factory,
        shard_count_fn=shard_count_fn,
        local_edge_cap=slab_cap,
        injector=injector,
    )


def build_imru_step(task, records, plan, mesh, mesh_spec):
    """Materialize the planned Listing-2 step (Fig. 5): map + sender-side
    early aggregation (with optional microbatching), the planned reduce
    collective schedule, and the update UDF.  Returns ``(step, records)``
    with the records device-placed (loop-invariant caching)."""

    from jax import lax

    reduce_sched = plan.reduce
    data_axes = tuple(
        a for a in ("data",) if mesh_spec.size(a) > 1
    ) or ("data",)
    n_mb = plan.microbatches

    def local_partial(records_shard: Any, model: Any) -> Any:
        """map + sender-side early aggregation over the local shard, with
        optional microbatching (Fig. 5 O5+O6)."""

        if n_mb <= 1:
            return task.map(records_shard, model)
        leaves0 = jax.tree_util.tree_leaves(records_shard)
        n_local = leaves0[0].shape[0]
        mb = max(1, n_local // n_mb)

        def body(acc, i):
            batch = jax.tree_util.tree_map(
                lambda x: lax.dynamic_slice_in_dim(x, i * mb, mb, 0),
                records_shard,
            )
            stat = task.map(batch, model)
            acc = jax.tree_util.tree_map(jnp.add, acc, stat)
            return acc, None

        zero_stat = jax.tree_util.tree_map(
            jnp.zeros_like,
            jax.eval_shape(
                lambda: task.map(
                    jax.tree_util.tree_map(lambda x: x[:mb], records_shard),
                    model,
                )
            ),
        )
        acc, _ = lax.scan(body, zero_stat, jnp.arange(n_local // mb))
        return acc

    if mesh is not None and any(
        mesh.shape.get(a, 1) > 1 for a in ("pod", "data")
    ):
        batch_axes = tuple(
            a for a in ("pod", "data") if mesh.shape.get(a, 1) > 1
        )
        records = jax.device_put(
            records, NamedSharding(mesh, P(batch_axes))
        )

        from jax.experimental.shard_map import shard_map

        in_specs = (
            jax.tree_util.tree_map(lambda _: P(batch_axes), records),
            P(),  # model replicated
            P(),  # j replicated
        )

        def sharded_step(records_shard, model, j):
            partial = local_partial(records_shard, model)
            total = reduce_tree(
                partial, reduce_sched,
                data_axes=tuple(a for a in ("data",) if a in batch_axes),
                pod_axis="pod",
            )
            return task.update(j, model, total)

        step_inner = shard_map(
            sharded_step, mesh=mesh,
            in_specs=in_specs, out_specs=P(),
            check_rep=False,
        )
        step = jit_hoisted(lambda model, j: step_inner(records, model, j))
    else:
        def step_fn(model, j):
            partial = local_partial(records, model)
            return task.update(j, model, partial)

        step = jit_hoisted(step_fn)

    return step, records
