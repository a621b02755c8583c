"""Physical JAX operators (paper Section 4, Figures 4–5).

This module contains the *runtime* counterparts of the planner's choices:
each named physical strategy from :mod:`repro.core.planner` has a concrete,
jit-able implementation here, so plans are executable objects rather than
paperware.  Everything is written mesh-polymorphic: with a trivial mesh the
same code runs single-device (CPU tests), with a real mesh it runs SPMD under
``shard_map``.

Contents:

* **Reduce schedules** (Fig. 5 O6/O8/O11, the "model volume property") —
  :func:`reduce_tree` applies a :class:`~repro.core.planner.ReduceSchedule`
  to a pytree of per-shard partial aggregates inside ``shard_map``:
  flat ``psum``, hierarchical per-axis ``psum`` (ICI before DCN),
  ``psum_scatter`` + pod-psum + ``all_gather`` (ZeRO-1 dataflow), and a k-ary
  ``ppermute`` latency tree for the cross-pod hop.
* **Gradient codecs** — bf16 and error-feedback int8 compression applied
  around the collective (planner's ``codec`` choice).
* **Pregel connectors** (Fig. 4 O13/O14/O15 and Fig. 9) — message-exchange
  strategies over a vertex-sharded graph:
  ``dense_psum`` (partial dense contribution vectors + psum_scatter),
  ``merging`` (sender-sorted buckets + ``all_to_all`` + segment-combine),
  ``hash_sort`` (``all_to_all`` + receiver-side sort + segment-combine).
* **Group-by / combine** primitives — sorted segment reduce and scatter-add,
  the two receiver-side grouping algorithms of Fig. 9.
* **Index join** (Fig. 4 O7) — gather on dense vertex ids (the B-tree probe).
* **Row-table primitives** — sorted uint32 row codes over padded
  ``[cap, arity]`` id columns: sort-merge join / exact set-difference /
  unique-run segmentation plus the ``grid_to_rows``/``rows_to_grid``
  boundary converters for the executor's sparse storage
  (planner ``storage-selection`` notes).

Consumers: the unified executor (:mod:`repro.core.executor`) assembles
these operators into both the Listing-1/2 fast-path pipelines
(``build_pregel_steps`` / ``build_imru_step``) and the generic dense-grid
GroupBy lowering (``segment_combine_sorted`` under the monoid registry).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.monoid import (
    CombineMonoid,
    generic_segment_combine,
    get_monoid,
)
from repro.core.planner import ReduceSchedule
from repro.kernels.segment_combine.ops import (
    kernel_eligible as _kernel_eligible,
    segment_combine as _segment_combine_kernel,
)

__all__ = [
    "psum_tree",
    "reduce_tree",
    "kary_tree_psum",
    "compress_bf16",
    "CompressionState",
    "compress_int8_ef",
    "decompress_int8",
    "segment_combine_sorted",
    "scatter_combine",
    "index_join",
    "dense_psum_exchange",
    "merging_exchange",
    "hash_sort_exchange",
    "compact_active_edges",
    "sparse_merging_exchange",
    "sparse_hash_sort_exchange",
    "fused_got_exchange",
    "COMBINE_OPS",
    "row_codes",
    "sort_row_codes",
    "unique_row_runs",
    "join_row_codes",
    "difference_row_codes",
    "grid_to_rows",
    "row_linear_index",
    "rows_to_grid",
    "row_hash_exchange",
]


# ---------------------------------------------------------------------------
# Combine ops usable by Pregel combiners and segment reduces
# ---------------------------------------------------------------------------
#
# COMBINE_OPS is the *hardware fast-path* table (XLA segment ops, scatter
# .at[] combines, psum-scatter, the Pallas kernel).  The open-ended set of
# aggregates lives in the monoid registry (:mod:`repro.core.monoid`): every
# ``op`` string below resolves through :func:`get_monoid`, and monoids whose
# ``kernel_op`` is None lower to the generic XLA monoid path instead.

COMBINE_OPS = {
    "sum": (jnp.add, 0.0),
    "max": (jnp.maximum, -jnp.inf),
    "min": (jnp.minimum, jnp.inf),
}


def _generic_combine(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    monoid: CombineMonoid,
    *,
    edge_active=None,
    flag_cols: int = 0,
    presorted: bool,
) -> jax.Array:
    """Rank-normalizing wrapper over :func:`generic_segment_combine`:
    scalar-payload monoids accept [E] / [E, ...] slabs (flattened to 2-D and
    restored); structured monoids require [E, W] exactly."""

    if values.ndim == 2:
        return generic_segment_combine(
            values, segment_ids, num_segments, monoid,
            edge_active=edge_active, flag_cols=flag_cols,
            presorted=presorted,
        )
    if monoid.structured or flag_cols:
        raise ValueError(
            f"monoid {monoid.name!r} needs [rows, width] payloads, got "
            f"shape {values.shape}"
        )
    flat = values.reshape(values.shape[0], -1)
    out = generic_segment_combine(
        flat, segment_ids, num_segments, monoid,
        edge_active=edge_active, presorted=presorted,
    )
    return out.reshape((num_segments,) + values.shape[1:])


# ---------------------------------------------------------------------------
# Reduce schedules (the aggregation-tree feature) — run inside shard_map
# ---------------------------------------------------------------------------


def _axes_present(axis_names: Sequence[str]) -> Tuple[str, ...]:
    """Filter axis names to those bound in the current shard_map context."""

    present = []
    for name in axis_names:
        try:
            lax.axis_index(name)  # raises NameError outside binding
            present.append(name)
        except NameError:
            continue
    return tuple(present)


def kary_tree_psum(x: jax.Array, axis: str, k: int = 4) -> jax.Array:
    """K-ary reduction tree over a named axis via ``ppermute`` rounds.

    The paper's 4-ary aggregation tree (Fig. 5 O8): each round, every group
    of ``k`` consecutive participants sends to its group leader; after
    ``ceil(log_k n)`` rounds rank 0 holds the total, which is then broadcast
    back.  Trades bandwidth (k·bytes per level, non-pipelined) for latency
    (log_k n hops instead of the ring's 2(n-1)), which wins for small
    payloads over high-latency (cross-pod) links.
    """

    n = lax.axis_size(axis)
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    stride = 1
    total = x
    while stride < n:
        # Members idx = leader + j*stride (j=1..k-1) send to their leader
        # (idx with group offset 0 at this level).
        group = stride * k
        partial = total
        for j in range(1, k):
            src_offset = j * stride
            # Each device receives from idx + src_offset (mod n).
            perm = [(int((i + src_offset) % n), int(i)) for i in range(n)]
            shifted = lax.ppermute(total, axis, perm)
            # Only leaders (idx % group == 0) whose source is within their
            # group and within range accumulate.
            is_leader = (idx % group) == 0
            src_valid = (idx + src_offset) < n
            take = jnp.logical_and(is_leader, src_valid)
            partial = partial + jnp.where(take, shifted, jnp.zeros_like(shifted))
        total = partial
        stride = group
    # Broadcast the root's total back to every member of the axis: mask all
    # non-root partials to zero and sum (ppermute cannot fan out 1->n).
    root_only = jnp.where(idx == 0, total, jnp.zeros_like(total))
    return lax.psum(root_only, axis)


def psum_tree(x: jax.Array, schedule: ReduceSchedule,
              data_axes: Tuple[str, ...] = ("data",),
              pod_axis: str = "pod") -> jax.Array:
    """Apply one reduce schedule to a single array (see :func:`reduce_tree`)."""

    data_axes = _axes_present(data_axes)
    pods = _axes_present((pod_axis,))

    if schedule.kind == "flat":
        axes = tuple(data_axes) + pods
        return lax.psum(x, axes) if axes else x
    if schedule.kind == "hierarchical":
        # Early aggregation within the pod (ICI), then across pods (DCN):
        # the paper's machine-local pre-aggregation + 1-level tree.
        out = lax.psum(x, data_axes) if data_axes else x
        if pods:
            out = lax.psum(out, pods)
        return out
    if schedule.kind == "kary_tree":
        out = lax.psum(x, data_axes) if data_axes else x
        if pods:
            out = kary_tree_psum(out, pods[0], schedule.kary)
        return out
    if schedule.kind == "scatter":
        # ZeRO-1 dataflow: reduce_scatter over data, reduce the shard across
        # pods, update happens on the shard, all_gather at the call site.
        # Here we express the pure reduction part; the sharded-update variant
        # is composed by the IMRU executor via ``reduce_scatter_tree``.
        out = x
        if data_axes:
            flat = out.reshape(-1)
            pad = (-flat.shape[0]) % _axes_size(data_axes)
            if pad:
                flat = jnp.pad(flat, (0, pad))
            shard = lax.psum_scatter(
                flat.reshape(_axes_size(data_axes), -1), data_axes,
                scatter_dimension=0, tiled=False,
            )
            if pods:
                shard = lax.psum(shard, pods)
            gathered = lax.all_gather(shard, data_axes, tiled=False)
            flat = gathered.reshape(-1)[: out.size]
            out = flat.reshape(out.shape)
        elif pods:
            out = lax.psum(out, pods)
        return out
    raise ValueError(f"unknown schedule {schedule.kind!r}")


def _axes_size(axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= lax.axis_size(a)
    return n


def reduce_tree(tree, schedule: ReduceSchedule,
                data_axes: Tuple[str, ...] = ("data",),
                pod_axis: str = "pod"):
    """Apply a reduce schedule to every leaf of a pytree of partials.

    Codec application (bf16 / int8 error-feedback) happens per-leaf around
    the collective; error feedback state is the caller's responsibility (see
    :mod:`repro.optim.compression` for the stateful wrapper).
    """

    def one(x):
        if schedule.codec == "bf16" and x.dtype == jnp.float32:
            y = x.astype(jnp.bfloat16)
            return psum_tree(y, schedule, data_axes, pod_axis).astype(x.dtype)
        return psum_tree(x, schedule, data_axes, pod_axis)

    return jax.tree_util.tree_map(one, tree)


# ---------------------------------------------------------------------------
# Gradient codecs
# ---------------------------------------------------------------------------


def compress_bf16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16)


@dataclass
class CompressionState:
    """Error-feedback residual for int8 compression (one leaf)."""

    residual: jax.Array


def compress_int8_ef(x: jax.Array, residual: jax.Array):
    """Error-feedback int8 quantization: q = round((x+r)/s), r' = x+r - s*q.

    The residual carries quantization error into the next step, which keeps
    SGD-style updates unbiased in the long run [Seide et al., 1-bit SGD].
    Returns (q_int8, scale, new_residual).
    """

    y = x + residual
    scale = jnp.maximum(jnp.max(jnp.abs(y)) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
    new_residual = y - q.astype(y.dtype) * scale
    return q, scale, new_residual


def decompress_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return q.astype(dtype) * scale


# ---------------------------------------------------------------------------
# Group-by / combine primitives (Fig. 9's two receiver algorithms)
# ---------------------------------------------------------------------------


def segment_combine_sorted(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    op: str = "sum",
    *,
    edge_active: Optional[jax.Array] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    flag_cols: int = 0,
) -> jax.Array:
    """Pre-clustered (sorted) group-by combine — the *merging* side of Fig. 9.

    Requires ``segment_ids`` sorted ascending; reduces consecutive runs.
    On TPU this dispatches to the Pallas kernel in
    :mod:`repro.kernels.segment_combine` (a scalar-prefetched work list of
    the (output tile, edge block) pairs whose id bands meet); elsewhere it
    lowers to
    ``jax.ops.segment_*`` with ``indices_are_sorted=True`` so XLA can use
    the cheap one-pass algorithm (the paper's pre-clustered group-by
    exploiting the order property).

    ``edge_active`` (optional bool[E]) is the semi-naive delta-frontier
    mask: rows outside the frontier are excluded from the combine, and the
    kernel path neither fetches nor computes edge blocks with no active
    row.  Empty segments differ by path
    (kernel: combine identity mapped to 0; XLA max/min: ±inf; generic
    monoids: the identity row) — Pregel callers gate them behind the
    ``got``-a-message mask either way.

    ``op`` names any registered monoid.  Monoids riding a hardware fast
    path (``kernel_op`` in sum/max/min) take the kernel/XLA code below;
    everything else lowers to the generic XLA monoid path.  ``flag_cols``
    marks trailing fused got-flag columns (see
    :func:`fused_got_exchange`), which generic monoids combine under
    ``max`` instead of the payload combine.
    """

    monoid = get_monoid(op)
    if monoid.kernel_op is None:
        return _generic_combine(
            values, segment_ids, num_segments, monoid,
            edge_active=edge_active, flag_cols=flag_cols, presorted=True,
        )
    op = monoid.kernel_op
    if use_kernel is None:
        # Shared auto-dispatch predicate (f32 and bf16 payloads: the kernel
        # accumulates in f32 and casts back, which would silently narrow
        # f64/int payloads — those stay on the XLA path).
        use_kernel = _kernel_eligible(values, interpret, op)
    if use_kernel:
        flat = values.reshape(values.shape[0], -1).astype(jnp.float32)
        out = _segment_combine_kernel(
            flat, segment_ids.astype(jnp.int32), num_segments, op,
            edge_active=edge_active, interpret=interpret, use_kernel=True,
        )
        return out.reshape((num_segments,) + values.shape[1:]).astype(
            values.dtype
        )
    indices_sorted = True
    if edge_active is not None:
        # num_segments is out of range for the scatter underneath
        # jax.ops.segment_* — excluded rows are dropped, not combined.
        # The remap interleaves out-of-range ids among the sorted runs, so
        # the sortedness hint must be dropped (XLA's one-pass sorted
        # reduction would mis-detect runs).
        segment_ids = jnp.where(edge_active, segment_ids, num_segments)
        indices_sorted = False
    if op == "sum":
        return jax.ops.segment_sum(
            values, segment_ids, num_segments,
            indices_are_sorted=indices_sorted,
        )
    if op == "max":
        return jax.ops.segment_max(
            values, segment_ids, num_segments,
            indices_are_sorted=indices_sorted,
        )
    if op == "min":
        return jax.ops.segment_min(
            values, segment_ids, num_segments,
            indices_are_sorted=indices_sorted,
        )
    raise ValueError(f"unsupported combine op {op!r}")


def scatter_combine(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    op: str = "sum",
    *,
    edge_active: Optional[jax.Array] = None,
    flag_cols: int = 0,
) -> jax.Array:
    """Unordered scatter-reduce — the *hash* (+sort-free) side of Fig. 9.

    No sortedness assumption: every row scatters into its destination slot.
    Rows where ``edge_active`` is False take an out-of-range destination and
    are dropped by the scatter.  Generic monoids (no ``kernel_op``) sort by
    destination and run the segmented-scan monoid path.
    """

    monoid = get_monoid(op)
    if monoid.kernel_op is None:
        return _generic_combine(
            values, segment_ids, num_segments, monoid,
            edge_active=edge_active, flag_cols=flag_cols, presorted=False,
        )
    op = monoid.kernel_op
    if edge_active is not None:
        segment_ids = jnp.where(edge_active, segment_ids, num_segments)
    fn, init = COMBINE_OPS[op]
    out = jnp.full((num_segments,) + values.shape[1:], init, values.dtype)
    if op == "sum":
        out = jnp.zeros((num_segments,) + values.shape[1:], values.dtype)
        return out.at[segment_ids].add(values)
    if op == "max":
        return out.at[segment_ids].max(values)
    return out.at[segment_ids].min(values)


def index_join(state: jax.Array, ids: jax.Array) -> jax.Array:
    """Index join (Fig. 4 O7): probe the dense id-indexed state by gather.

    ``state`` is the B-tree analogue — a dense array indexed by vertex id;
    the probe is O(1) per row instead of the logical max-over-temporal scan.
    """

    return jnp.take(state, ids, axis=0)


# ---------------------------------------------------------------------------
# Pregel message-exchange connectors (Fig. 4 connectors, Fig. 9 variants)
# ---------------------------------------------------------------------------
#
# Contract: vertices are dense ids [0, N) partitioned contiguously over the
# flattened data axes; each shard holds n_local = N / n_shards vertices.
# ``messages`` are per-edge contributions computed on the *source* shard:
#   dst_ids  int32[E_local]   — global destination vertex ids
#   payload  f32[E_local, ...]— message payloads
# Every connector returns f32[n_local, ...] of combined inbound messages for
# the shard's own vertices.  All three are jit/shard_map compatible with
# static shapes (TPU-native dense formulation of the sparse exchange).


def compact_active_edges(
    edge_mask: jax.Array, cap: int
) -> Tuple[jax.Array, jax.Array]:
    """Sort-free fixed-capacity compaction of the active-edge frontier.

    Static-shape TPU formulation of "gather the indices where the mask is
    set": a prefix sum over the mask followed by a vectorized binary search
    that finds, for each of the ``cap`` output slots, the edge where the
    running count first reaches it — no sort, no scatter, O(E + cap·log E),
    jit/shard_map-safe.  Returns ``(idx, valid)`` where
    ``idx`` is int32[cap] (edge index, or E for empty slots) and ``valid``
    marks occupied slots.  Active edges beyond ``cap`` are dropped: the
    caller (the adaptive driver) picks ``cap`` from the measured frontier
    size, so overflow means it re-runs dense, never silently loses messages.
    """

    E = edge_mask.shape[0]
    if E == 0:
        # Zero-edge slab: nothing to compact.  Every slot is empty and
        # carries the sentinel index E (== 0); ``csum[-1]`` below would
        # read out of bounds on an empty prefix sum.
        return (
            jnp.zeros((cap,), jnp.int32),
            jnp.zeros((cap,), jnp.bool_),
        )
    csum = jnp.cumsum(edge_mask.astype(jnp.int32))
    # Slot s holds the edge where the running count first reaches s+1: a
    # vectorized binary search over the monotone prefix sums — O(cap log E),
    # no scatter (element-wise scatters serialize badly on some backends).
    idx = jnp.searchsorted(
        csum, jnp.arange(1, cap + 1, dtype=csum.dtype), side="left"
    ).astype(jnp.int32)
    valid = jnp.arange(cap, dtype=csum.dtype) < csum[-1]
    idx = jnp.where(valid, idx, E)
    return idx, valid


def fused_got_exchange(
    exchange: Callable[[jax.Array], jax.Array],
    payload: jax.Array,
    edge_valid: jax.Array,
    op: str,
) -> Tuple[jax.Array, jax.Array]:
    """One exchange for ``(inbox, got)`` instead of two.

    The Pregel executor needs both the combined inbox and the
    got-a-message mask (the L7 non-null check).  Running the connector twice
    doubles the collective count per superstep; instead we append a *flag*
    column that carries 1.0 on every occupied slot and travels (and
    combines) with the payload:

    * ``sum``  — flags accumulate to the message count; ``got = flag > 0``.
    * ``max``  — combined flag is 1.0 where any message arrived; empty
      destinations read the identity (-inf on the XLA path, 0 on the Pallas
      kernel path) — both fail ``flag > 0``.
    * ``min``  — combined flag is exactly 1.0 where any message arrived;
      empty destinations read +inf (XLA) or 0 (kernel) — both fail
      ``flag == 1.0`` (the ``> 0`` test would wrongly pass on +inf).
    * generic monoids — the flag column combines under ``max`` (the
      monoid's ``combine_slab`` splits payload and flag columns), so the
      combined flag is 1.0 exactly where any message arrived and empty
      destinations read the 0 flag identity; ``got = flag > 0``.

    ``exchange`` maps the fused ``[E, F+1]`` slab through the connector;
    the caller closes over destination ids / axes / masks (and passes
    ``flag_cols=1`` so generic monoids keep the flag out of the payload
    combine).
    """

    flat = payload.reshape(payload.shape[0], -1)
    flag = jnp.where(edge_valid, 1.0, 0.0).astype(flat.dtype)
    fused = jnp.concatenate([flat, flag[:, None]], axis=1)
    out = exchange(fused)
    inbox = out[..., :-1].reshape((out.shape[0],) + payload.shape[1:])
    got = get_monoid(op).got_mask(out[..., -1])
    return inbox, got


def sparse_merging_exchange(
    dst_ids: jax.Array,
    payload: jax.Array,
    edge_valid: jax.Array,
    n_vertices: int,
    axes: Tuple[str, ...],
    op: str = "sum",
    bucket_cap: Optional[int] = None,
    flag_cols: int = 0,
) -> jax.Array:
    """Frontier-compacted variant of :func:`merging_exchange`.

    Operates on a ``cap``-sized compacted edge slab (see
    :func:`compact_active_edges`): ``edge_valid`` marks occupied slots;
    empty slots are excluded from the combine (and from the Pallas kernel's
    visited blocks).  Exchange + merge cost scales with the *frontier*
    size, not E.
    """

    return merging_exchange(
        dst_ids, payload, n_vertices, axes, op, bucket_cap,
        edge_mask=edge_valid, flag_cols=flag_cols,
    )


def sparse_hash_sort_exchange(
    dst_ids: jax.Array,
    payload: jax.Array,
    edge_valid: jax.Array,
    n_vertices: int,
    axes: Tuple[str, ...],
    op: str = "sum",
    bucket_cap: Optional[int] = None,
    flag_cols: int = 0,
) -> jax.Array:
    """Frontier-compacted variant of :func:`hash_sort_exchange` (same slab
    contract as :func:`sparse_merging_exchange`)."""

    return hash_sort_exchange(
        dst_ids, payload, n_vertices, axes, op, bucket_cap,
        edge_mask=edge_valid, flag_cols=flag_cols,
    )


def dense_psum_exchange(
    dst_ids: jax.Array,
    payload: jax.Array,
    n_vertices: int,
    axes: Tuple[str, ...],
    op: str = "sum",
    edge_mask: Optional[jax.Array] = None,
    flag_cols: int = 0,
) -> jax.Array:
    """Dense partial-vector exchange: each shard scatter-combines its
    outbound messages into a dense length-N vector, then a single
    ``psum_scatter`` both reduces and re-partitions to the owners.

    Collective volume: N*payload_bytes per shard independent of edge count —
    the paper's observation that shuffling only the (dense) rank
    contributions beats re-shuffling the graph.  Best when the graph is
    dense enough that most destinations receive a message anyway.

    ``edge_mask`` (the frontier-masked path): inactive edges are dropped by
    the scatter, so a semi-naive plan can run the dense connector without
    changing the fixpoint.
    """

    monoid = get_monoid(op)
    dense = scatter_combine(
        payload, dst_ids, n_vertices, op, edge_active=edge_mask,
        flag_cols=flag_cols,
    )
    axes = _axes_present(axes)
    if not axes:
        return dense
    n_shards = _axes_size(axes)
    grouped = dense.reshape((n_shards, n_vertices // n_shards) + dense.shape[1:])
    if monoid.kernel_op != "sum":
        # psum_scatter only sums; for max/min — and any generic monoid —
        # fall back to all-reduce-style combine via all_gather (rare in
        # practice — PageRank/BGD are sums).
        gathered = lax.all_gather(grouped, axes, tiled=False)
        if monoid.kernel_op is not None:
            fn, _ = COMBINE_OPS[monoid.kernel_op]
        else:
            fn = lambda a, b: monoid.combine_slab(a, b, flag_cols)
        combined = functools.reduce(
            fn, [gathered[i] for i in range(gathered.shape[0])]
        )
        idx = _linear_shard_index(axes)
        return combined[idx]
    return lax.psum_scatter(grouped, axes, scatter_dimension=0, tiled=False)


def _linear_shard_index(axes: Tuple[str, ...]) -> jax.Array:
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def _bucket_by_owner(
    dst_ids: jax.Array,
    payload: jax.Array,
    n_vertices: int,
    n_shards: int,
    bucket_cap: int,
    presorted: bool,
    edge_active=None,
):
    """Pack messages into fixed-capacity per-owner buckets for all_to_all.

    Returns (ids[n_shards, cap], vals[n_shards, cap, ...], valid mask).
    Overflow beyond ``bucket_cap`` is dropped — capacity is a planner-chosen
    static bound (tests use cap >= E_local so nothing drops), mirroring the
    fixed-size frame buffers of the Hyracks connectors.

    Rows excluded by ``edge_active`` take the out-of-range owner
    ``n_shards``: they sort after every real row, never compete with real
    messages for bucket slots, and their scatter writes fall out of bounds
    and are dropped — so a ``bucket_cap`` sized to the active frontier
    stays safe.
    """

    n_local_v = n_vertices // n_shards
    owner = jnp.clip(dst_ids // n_local_v, 0, n_shards - 1)
    if edge_active is not None:
        owner = jnp.where(edge_active, owner, n_shards)
    order = jnp.argsort(owner * (n_vertices + 1) + (dst_ids if presorted else 0))
    owner_s = owner[order]
    ids_s = dst_ids[order]
    vals_s = payload[order]
    # Rank within each owner bucket: position minus first index of the owner
    # run (owner_s is sorted, so searchsorted finds the run start in O(log E)).
    pos = jnp.arange(owner_s.shape[0], dtype=jnp.int32)
    run_start = jnp.searchsorted(owner_s, owner_s, side="left").astype(jnp.int32)
    rank = pos - run_start
    slot = owner_s * bucket_cap + jnp.minimum(rank, bucket_cap - 1)
    keep = rank < bucket_cap
    ids_b = jnp.full((n_shards * bucket_cap,), -1, dtype=ids_s.dtype)
    ids_b = ids_b.at[slot].set(jnp.where(keep, ids_s, -1))
    vals_b = jnp.zeros((n_shards * bucket_cap,) + vals_s.shape[1:], vals_s.dtype)
    vals_b = vals_b.at[slot].set(
        jnp.where(
            keep.reshape((-1,) + (1,) * (vals_s.ndim - 1)), vals_s, 0
        )
    )
    return (
        ids_b.reshape(n_shards, bucket_cap),
        vals_b.reshape((n_shards, bucket_cap) + vals_s.shape[1:]),
    )


def _sparse_exchange(
    dst_ids, payload, n_vertices, axes, op, bucket_cap, presorted,
    edge_active=None, flag_cols=0,
):
    axes = _axes_present(axes)
    if not axes:
        if presorted:
            order = jnp.argsort(dst_ids)
            act = None if edge_active is None else edge_active[order]
            return segment_combine_sorted(
                payload[order], dst_ids[order], n_vertices, op,
                edge_active=act, flag_cols=flag_cols,
            )
        return scatter_combine(
            payload, dst_ids, n_vertices, op, edge_active=edge_active,
            flag_cols=flag_cols,
        )

    # Sharded path: excluded rows are dropped at bucket packing (they take
    # an out-of-range owner and never travel — see _bucket_by_owner).
    n_shards = _axes_size(axes)
    n_local_v = n_vertices // n_shards
    ids_b, vals_b = _bucket_by_owner(
        dst_ids, payload, n_vertices, n_shards, bucket_cap, presorted,
        edge_active=edge_active,
    )
    # all_to_all over (possibly multiple) axes: transpose shard-major blocks.
    if len(axes) == 1:
        ids_x = lax.all_to_all(ids_b, axes[0], split_axis=0, concat_axis=0,
                               tiled=True)
        vals_x = lax.all_to_all(vals_b, axes[0], split_axis=0, concat_axis=0,
                                tiled=True)
    else:
        # Flatten multiple data axes into sequential exchanges.
        ids_x, vals_x = ids_b, vals_b
        for ax in axes:
            ids_x = lax.all_to_all(ids_x, ax, 0, 0, tiled=True)
            vals_x = lax.all_to_all(vals_x, ax, 0, 0, tiled=True)

    flat_ids = ids_x.reshape(-1)
    flat_vals = vals_x.reshape((-1,) + vals_x.shape[2:])
    base = _linear_shard_index(axes) * n_local_v
    local = jnp.where(flat_ids >= 0, flat_ids - base, n_local_v)
    valid = jnp.logical_and(local >= 0, local < n_local_v)
    local = jnp.where(valid, local, n_local_v)  # spill row n_local_v

    if presorted:
        # Receiver merges pre-sorted runs: sorting nearly-sorted ids is the
        # merge; then a sorted segment reduce (the "merging connector").
        # Empty bucket slots (id -1) are passed as the receiver-side frontier
        # mask: on TPU the Pallas combiner skips slab blocks made entirely
        # of padding, so receiver compute also scales
        # with the frontier, not with n_shards * bucket_cap.
        order = jnp.argsort(local)
        local_s, vals_s = local[order], flat_vals[order]
        occupied = (flat_ids >= 0)[order]
        out = segment_combine_sorted(
            vals_s, local_s, n_local_v + 1, op, edge_active=occupied,
            flag_cols=flag_cols,
        )
    else:
        out = scatter_combine(
            flat_vals, local, n_local_v + 1, op,
            edge_active=(flat_ids >= 0), flag_cols=flag_cols,
        )
    return out[:n_local_v]


def merging_exchange(dst_ids, payload, n_vertices, axes,
                     op="sum", bucket_cap=None, edge_mask=None,
                     flag_cols=0):
    """The hash-partitioning *merging* connector (Fig. 4): sender-side
    sort-by-destination + all_to_all + receiver-side ordered merge/combine.

    ``edge_mask`` (the frontier-masked path) excludes inactive edges from
    the combine.  Single-shard, the mask reaches the receiver combine — on
    TPU that is the Pallas ``segment_combine`` kernel, which skips edge
    blocks with no active row.  Sharded, masked rows are
    dropped earlier still, at sender-side bucket packing, so they never
    travel the all_to_all."""

    cap = bucket_cap or dst_ids.shape[0]
    return _sparse_exchange(
        dst_ids, payload, n_vertices, axes, op, cap, True,
        edge_active=edge_mask, flag_cols=flag_cols,
    )


def hash_sort_exchange(dst_ids, payload, n_vertices, axes,
                       op="sum", bucket_cap=None, edge_mask=None,
                       flag_cols=0):
    """The hash connector + explicit receiver-side grouping (Fig. 9 variant):
    all_to_all in arrival order, receiver scatter-combines (no order
    property)."""

    cap = bucket_cap or dst_ids.shape[0]
    return _sparse_exchange(
        dst_ids, payload, n_vertices, axes, op, cap, False,
        edge_active=edge_mask, flag_cols=flag_cols,
    )


# ---------------------------------------------------------------------------
# Row-table primitives (sparse storage for the generic executor)
# ---------------------------------------------------------------------------
#
# A *row table* is the compacted sparse counterpart of the executor's dense
# vertex-domain grids: a fixed-capacity slab of id columns ``int32[cap, k]``
# plus a validity mask ``bool[cap]`` (value columns ride alongside as
# ``[cap]`` arrays owned by the caller).  Every primitive below is
# static-shape and jit/shard_map-safe; set semantics ride on *row codes* —
# the lexicographic uint32 encoding of a row's id tuple — so Join is a
# sort-merge over codes, AntiJoin is an exact searchsorted set-difference,
# and GroupBy/dedupe are unique-run segment combines.
#
# Capacity discipline: joins expand into a caller-chosen ``out_cap`` and
# report a traced ``overflow`` flag instead of silently dropping rows; the
# executor accumulates those flags and falls back to the dense grids when
# any fires (lossless overflow policy, see ``core/planner.plan_program``).

# Invalid rows sort with this key.  A *valid* row may legitimately carry the
# same code (the all-max id tuple when domain**k == 2**32): the sort places
# valid rows first among equal keys, so the valid region is always a prefix
# of length ``n_valid`` and membership tests stay exact.
_ROW_SENTINEL = np.uint32(0xFFFFFFFF)


def row_codes(ids: jax.Array, n: int) -> jax.Array:
    """Lexicographic uint32 code of each id row: ``sum ids[:, i] * n**(k-1-i)``.

    Requires ``n ** k <= 2**32`` (checked statically) so codes are unique;
    the executor's planner refuses row-table storage beyond that.
    """

    cap, k = ids.shape
    if k and float(n) ** k > 4294967296.0:
        raise ValueError(
            f"row_codes: domain**arity = {n}**{k} exceeds the 2^32 row-code "
            "space (row-table storage caps key arity by domain size)"
        )
    code = jnp.zeros((cap,), jnp.uint32)
    for i in range(k):
        code = code * jnp.uint32(n) + ids[:, i].astype(jnp.uint32)
    return code


def sort_row_codes(
    codes: jax.Array, valid: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort a row table by code with valid rows first.

    Returns ``(perm, sorted_key, n_valid)``: ``perm`` reorders any per-row
    array into sorted order, ``sorted_key`` is monotone (valid rows'
    ascending codes, then ``_ROW_SENTINEL`` for the invalid suffix), and the
    first ``n_valid`` sorted slots are exactly the valid rows.
    """

    skey = jnp.where(valid, codes, _ROW_SENTINEL)
    # Secondary key puts valid rows before invalid ones among equal codes
    # (lexsort: last key is primary).
    perm = jnp.lexsort(((~valid).astype(jnp.uint8), skey)).astype(jnp.int32)
    sorted_key = jnp.where(
        valid[perm], codes[perm], _ROW_SENTINEL
    )
    return perm, sorted_key, jnp.sum(valid.astype(jnp.int32))


def unique_row_runs(
    sorted_key: jax.Array, n_valid: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """First-occurrence mask and segment ids of the unique runs in a sorted
    key array (valid prefix only).  ``seg[i]`` numbers the run row ``i``
    belongs to; rows past ``n_valid`` alias the last run and must be masked
    by the caller (``edge_active``)."""

    cap = sorted_key.shape[0]
    ar = jnp.arange(cap, dtype=jnp.int32)
    prev = jnp.concatenate([sorted_key[:1], sorted_key[:-1]])
    in_valid = ar < n_valid
    is_new = in_valid & ((ar == 0) | (sorted_key != prev))
    seg = jnp.maximum(jnp.cumsum(is_new.astype(jnp.int32)) - 1, 0)
    return is_new, seg


def join_row_codes(
    l_codes: jax.Array,
    l_valid: jax.Array,
    r_codes: jax.Array,
    r_valid: jax.Array,
    out_cap: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort-merge equi-join of two row tables on their codes.

    The right table is sorted once; each left row finds its matching run by
    binary search, and a prefix sum over per-row match counts lays the pairs
    out densely into ``out_cap`` slots (the static-shape pair expansion).
    Returns ``(li, ri, valid, overflow)``: left/right row indices per output
    slot, the slot validity mask, and a traced flag set when the true pair
    count exceeds ``out_cap`` (pairs beyond the cap are dropped — the caller
    must honor the flag).
    """

    cap_l, cap_r = l_codes.shape[0], r_codes.shape[0]
    perm_r, r_skey, r_nv = sort_row_codes(r_codes, r_valid)
    start = jnp.searchsorted(r_skey, l_codes, side="left").astype(jnp.int32)
    end = jnp.searchsorted(r_skey, l_codes, side="right").astype(jnp.int32)
    # Clamp to the valid prefix: a left code equal to the sentinel would
    # otherwise also "match" the invalid suffix.
    end = jnp.minimum(end, r_nv)
    cnt = jnp.where(l_valid, jnp.maximum(end - start, 0), 0)
    offs = jnp.cumsum(cnt)
    total = offs[-1]
    overflow = jnp.logical_or(total > out_cap, total < 0)
    t = jnp.arange(out_cap, dtype=jnp.int32)
    li = jnp.searchsorted(offs, t, side="right").astype(jnp.int32)
    li = jnp.minimum(li, cap_l - 1)
    before = offs[li] - cnt[li]
    rpos = start[li] + (t - before)
    ri = perm_r[jnp.clip(rpos, 0, cap_r - 1)]
    valid = t < total
    return li, ri, valid, overflow


def difference_row_codes(
    l_codes: jax.Array,
    l_valid: jax.Array,
    r_codes: jax.Array,
    r_valid: jax.Array,
) -> jax.Array:
    """Exact set-difference membership mask: True for valid left rows whose
    code has NO valid right row (the AntiJoin keep-mask).  Capacity-free —
    the left table is returned in place, only the mask changes."""

    _, r_skey, r_nv = sort_row_codes(r_codes, r_valid)
    cap_r = r_skey.shape[0]
    pos = jnp.searchsorted(r_skey, l_codes, side="left").astype(jnp.int32)
    posc = jnp.minimum(pos, cap_r - 1)
    member = jnp.logical_and(pos < r_nv, r_skey[posc] == l_codes)
    return jnp.logical_and(l_valid, jnp.logical_not(member))


def grid_to_rows(
    present: jax.Array, cap: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Compact a dense presence grid into a row table (``to_rows`` boundary
    converter).  Returns ``(ids, valid, lin, overflow)``: id columns
    ``int32[cap, k]``, slot validity, the clamped linear cell index per slot
    (for gathering value grids via ``grid.reshape(-1)[lin]``), and the
    traced overflow flag (more present cells than ``cap``)."""

    shape = present.shape
    k = len(shape)
    if k == 0:
        valid = jnp.zeros((cap,), jnp.bool_).at[0].set(
            jnp.asarray(present, jnp.bool_)
        )
        return (
            jnp.zeros((cap, 0), jnp.int32),
            valid,
            jnp.zeros((cap,), jnp.int32),
            jnp.asarray(False),
        )
    flat = present.reshape((-1,))
    size = flat.shape[0]
    idx, valid = compact_active_edges(flat, cap)
    overflow = jnp.sum(flat.astype(jnp.int32)) > cap
    lin = jnp.minimum(idx, size - 1)
    unr = jnp.unravel_index(lin, shape)
    ids = jnp.stack([u.astype(jnp.int32) for u in unr], axis=-1)
    return ids, valid, lin, overflow


def row_linear_index(ids: jax.Array, valid: jax.Array, n: int) -> jax.Array:
    """Linear dense-grid cell index of each row (``int32[cap]``); invalid
    rows get the out-of-range sentinel ``n**k`` so ``mode='drop'`` scatters
    ignore them.  Only meaningful when the dense grid is materializable
    (``n**k`` within int32)."""

    cap, k = ids.shape
    size = int(n) ** k
    lin = jnp.zeros((cap,), jnp.int32)
    for i in range(k):
        lin = lin * jnp.int32(n) + ids[:, i].astype(jnp.int32)
    return jnp.where(valid, lin, jnp.int32(size))


def rows_to_grid(ids: jax.Array, valid: jax.Array, n: int) -> jax.Array:
    """Scatter a row table back onto the dense presence grid (``to_grid``
    boundary converter)."""

    k = ids.shape[1]
    if k == 0:
        return jnp.any(valid)
    size = int(n) ** k
    lin = row_linear_index(ids, valid, n)
    flat = jnp.zeros((size,), jnp.bool_).at[lin].set(True, mode="drop")
    return flat.reshape((n,) * k)


def row_hash_exchange(
    owner: jax.Array,
    payload,
    valid: jax.Array,
    n_shards: int,
    bucket_cap: int,
    axes: Tuple[str, ...],
):
    """Key-hash bucket all-to-all for generic row slabs (the explicit
    sharded connector of the row-table GroupBy/Join lowering).

    Each valid row carries a destination shard ``owner`` (its key hash mod
    ``n_shards``, chosen by the caller); rows are packed into fixed-capacity
    ``bucket_cap`` per-owner buckets and exchanged with a tiled
    ``all_to_all`` per mesh axis, mirroring :func:`_bucket_by_owner` /
    :func:`_sparse_exchange` but for an arbitrary pytree ``payload`` of
    ``[cap, ...]`` leaves rather than a single (ids, vals) pair.

    Returns ``(payload_x, valid_x, overflow)``: the received flat
    ``[n_shards * bucket_cap, ...]`` payload pytree, its validity mask, and
    a traced flag set when any *valid* row exceeded its bucket's capacity
    (dropped rows — the caller must honor the flag: the executor folds it
    into the lossless dense-fallback overflow policy).

    Invalid rows take the out-of-range owner ``n_shards``: they sort after
    every real row, never compete for bucket slots, and their scatter
    writes fall out of bounds and are dropped (``mode='drop'``).
    """

    axes = _axes_present(axes)
    cap = owner.shape[0]
    owner = jnp.where(valid, owner.astype(jnp.int32), jnp.int32(n_shards))
    order = jnp.argsort(owner)
    owner_s = owner[order]
    pos = jnp.arange(cap, dtype=jnp.int32)
    run_start = jnp.searchsorted(owner_s, owner_s, side="left").astype(jnp.int32)
    rank = pos - run_start
    keep = rank < bucket_cap
    # A valid row beyond its bucket's capacity is dropped in transit.
    overflow = jnp.any(jnp.logical_and(owner_s < n_shards, ~keep))
    # Dropped and invalid rows scatter out of range (mode='drop').
    slot = jnp.where(
        jnp.logical_and(keep, owner_s < n_shards),
        owner_s * bucket_cap + rank,
        jnp.int32(n_shards * bucket_cap),
    )

    def pack(leaf):
        leaf_s = leaf[order]
        buf = jnp.zeros((n_shards * bucket_cap,) + leaf.shape[1:], leaf.dtype)
        return buf.at[slot].set(leaf_s, mode="drop").reshape(
            (n_shards, bucket_cap) + leaf.shape[1:]
        )

    packed = jax.tree_util.tree_map(pack, payload)
    valid_b = jnp.zeros((n_shards * bucket_cap,), jnp.bool_)
    valid_b = valid_b.at[slot].set(True, mode="drop").reshape(
        (n_shards, bucket_cap)
    )

    def exchange(leaf):
        for ax in axes:
            leaf = lax.all_to_all(leaf, ax, 0, 0, tiled=True)
        return leaf

    packed_x = jax.tree_util.tree_map(exchange, packed)
    valid_x = exchange(valid_b)
    flat = jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((n_shards * bucket_cap,) + leaf.shape[2:]),
        packed_x,
    )
    return flat, valid_x.reshape(-1), overflow
