"""Public wrapper for the sorted segment combiner.

Auto-selects the Pallas kernel on TPU (or interpret mode when requested) and
the jnp reference elsewhere — same dispatch contract as
:mod:`repro.kernels.flash_attention.ops`.  Registered monoids without a
hardware fast path (``kernel_op`` is None — argmin, topk, logsumexp, ...)
lower to the generic XLA monoid path instead of the kernel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.monoid import generic_segment_combine, get_monoid
from repro.kernels.segment_combine.kernel import segment_combine_pallas
from repro.kernels.segment_combine.ref import segment_combine_reference

__all__ = ["segment_combine", "kernel_eligible"]

_FAST_OPS = ("sum", "max", "min")


def kernel_eligible(
    values: jax.Array, interpret: Optional[bool], op: str = "sum"
) -> bool:
    """Auto-dispatch predicate shared by every segment-combine entry point
    (this wrapper and ``physical.segment_combine_sorted``): the Pallas
    kernel runs on TPU (or in interpret mode) for f32 payloads, and for
    bf16 payloads too — the kernel always accumulates in f32 and casts the
    result back to the payload dtype, so bf16 loses no more precision than
    the XLA fallback.  Wider/integer dtypes (f64, ints) would be silently
    narrowed by the f32 accumulator and stay on the XLA path; such callers
    can still opt in explicitly with ``use_kernel=True``.

    Eligibility by op: the kernel implements sum, max and min, and every
    registered monoid whose ``kernel_op`` names one of them.  A monoid with
    no ``kernel_op`` (argmin, topk, logsumexp, ...) never reaches the
    kernel on any backend; the planner records that choice as the
    ``combine-monoid(<op>, ..., xla-generic)`` plan note."""

    if op not in _FAST_OPS and get_monoid(op).kernel_op is None:
        return False
    return (
        jax.default_backend() == "tpu" or bool(interpret)
    ) and values.dtype in (jnp.float32, jnp.bfloat16)


def segment_combine(
    values: jax.Array,
    segment_ids: jax.Array,
    n_segments: int,
    op: str = "sum",
    *,
    edge_active: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    use_kernel: Optional[bool] = None,
) -> jax.Array:
    """``edge_active`` (optional bool[E]) is the delta-frontier mask: rows
    outside the frontier are excluded from the combine, and the Pallas path
    neither fetches nor computes edge blocks with no active row.  The
    sharded sparse connectors reuse the same mask for their receiver slabs
    (empty all-to-all bucket slots), so receiver-side combine work also
    scales with the frontier.  Auto-dispatch (``use_kernel=None``) follows
    :func:`kernel_eligible`; monoids without a ``kernel_op`` fast path go
    to the generic XLA monoid path (sorted-segment associative scan).
    """

    monoid = get_monoid(op)
    if monoid.kernel_op is None:
        return generic_segment_combine(
            values, segment_ids, n_segments, monoid,
            edge_active=edge_active, presorted=True,
        )
    op = monoid.kernel_op
    if use_kernel is None:
        use_kernel = kernel_eligible(values, interpret, op)
    if not use_kernel:
        return segment_combine_reference(
            values, segment_ids, n_segments, op, edge_active=edge_active
        )
    return segment_combine_pallas(
        values, segment_ids, n_segments, op, edge_active=edge_active,
        interpret=bool(interpret) and jax.default_backend() != "tpu",
    )
