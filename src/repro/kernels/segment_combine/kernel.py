"""Pallas TPU kernel: sorted segment combine (the Pregel message combiner).

The TPU-native re-think of the paper's pre-clustered group-by (Fig. 4
O14/O15): no scatter, no atomics.  Because ``segment_ids`` is sorted, each
edge block touches a *contiguous* range of output segments, and each output
tile is touched by a contiguous run of edge blocks.

Layout.  Edges are lane-dense: ids are an ``int32[rows, 128]`` array and the
payload an ``f32[F, rows, 128]`` array (one plane per feature), so a narrow
payload (PageRank's F=1) is never padded out to 128 lanes.  An edge block is
``block_rows x 128`` edges; an output tile is ``tile_n`` segments of the
``[F, n_out]`` result.

Work list.  The grid walks a flat list of (tile, edge-block) work items:
every pair whose id band intersects, plus one idle item for each tile no
block reaches, ordered by tile and then block.  Sorted ids bound its length
by ``n_blocks + n_tiles``, so the grid is linear in the input instead of the
``n_tiles x n_blocks`` band scan.  The list is built with XLA before the
call and scalar-prefetched to SMEM as two int32 tables: the tile of each
item, and its block index with a "compute" bit.  Consecutive items of one
tile keep the output block resident; the accumulator is initialised at a
tile's first item and written out at its last.

Per item, each 128-lane row of ids is compared against the tile's segment
columns (``(tile_n, 128)`` masks) and the payload folds into a
``(tile_n, 128)`` VPU accumulator under sum/max/min; the 128 lanes are
reduced once per tile.  The same code serves every op — there is no 3-D
broadcast and no minor-dim reshape for Mosaic to lower.

Semi-naive (delta-frontier) evaluation masks inactive edges to id -1 before
blocking.  A block with no live edge is a work item whose compute bit is off
and whose block index repeats the last computed block, so the pipeline
neither computes nor fetches anything for it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["segment_combine_pallas", "DEFAULT_BLOCK_ROWS", "DEFAULT_TILE_N"]

LANES = 128
DEFAULT_BLOCK_ROWS = 16   # an edge block is 16 x 128 = 2048 edges
DEFAULT_TILE_N = 128
# Two int32 SMEM tables of this many items take 512 KiB of the chip's 1 MiB.
MAX_WORK_ITEMS = 1 << 16
F_GROUP = 8               # payload features per grid row (sublane tile)

_IDENT = {"sum": 0.0, "max": -jnp.inf, "min": jnp.inf}
_FOLD = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _tiling(n_edges: int, n_segments: int):
    """Block rows and tile width whose work list fits ``MAX_WORK_ITEMS``.

    At 2^26 edges and 2^22 segments the defaults give 32768 + 32768 items.
    Larger inputs widen whichever of the two dimensions has more items."""

    rows = min(DEFAULT_BLOCK_ROWS, max(8, 8 * _cdiv(_cdiv(n_edges, LANES), 8)))
    tile_n = DEFAULT_TILE_N
    while (_cdiv(n_edges, rows * LANES) + _cdiv(n_segments, tile_n)
           > MAX_WORK_ITEMS):
        if _cdiv(n_edges, rows * LANES) >= _cdiv(n_segments, tile_n):
            rows *= 2
        else:
            tile_n *= 2
    return rows, tile_n


def _work_list(ids2, n_blocks: int, rows: int, n_tiles: int, tile_n: int):
    """The (tile, block) work items, as the two scalar-prefetch tables.

    Valid ids are sorted, so after a running max of each block's upper id
    bound and a reverse running min of its lower bound (which gives blocks
    with no valid id an empty or one-tile band), the blocks whose band meets
    tile ``t`` are the contiguous range ``[bs[t], be[t])``.  Consecutive
    bands share at most one tile, so the items number at most
    ``n_blocks + n_tiles - 1``."""

    n_work = n_blocks + n_tiles
    ids_b = ids2.reshape(n_blocks, rows * LANES)
    valid = ids_b >= 0
    live = jnp.any(valid, axis=1)
    lo = jnp.min(jnp.where(valid, ids_b, n_tiles * tile_n), axis=1)
    hi = jnp.max(jnp.where(valid, ids_b + 1, 0), axis=1)
    lo = lax.cummin(lo, reverse=True)
    hi = lax.cummax(hi)
    # bs[t]: blocks with hi <= t*tile_n; be[t]: blocks with lo < (t+1)*tile_n
    bs = jnp.cumsum(jnp.bincount((hi + tile_n - 1) // tile_n,
                                 length=n_tiles + 1))[:n_tiles]
    be = jnp.cumsum(jnp.bincount(jnp.minimum(lo // tile_n, n_tiles),
                                 length=n_tiles + 1))[:n_tiles]
    n_real = jnp.maximum(be - bs, 0)
    count = jnp.maximum(n_real, 1)          # an idle item for an empty tile
    start = jnp.cumsum(count) - count
    tile = jnp.cumsum(
        jnp.zeros((n_work,), jnp.int32).at[start].add(1)
    ) - 1                                   # items past the end: last tile
    k = jnp.arange(n_work, dtype=jnp.int32)
    j = k - start[tile]
    blk = jnp.clip(bs[tile] + j, 0, n_blocks - 1)
    compute = jnp.logical_and(j < n_real[tile], live[blk])
    # A skipped item re-points at the last computed block: no new DMA.
    fetch = jnp.maximum(lax.cummax(jnp.where(compute, blk, -1)), 0)
    code = fetch * 2 + compute.astype(jnp.int32)
    return tile.astype(jnp.int32), code.astype(jnp.int32)


def _kernel(tile_ref, code_ref, ids_ref, val_ref, out_ref, acc,
            *, op, tile_n, rows, n_feat):
    k = pl.program_id(1)
    last_k = pl.num_programs(1) - 1
    t = tile_ref[k]
    first = jnp.logical_or(k == 0, tile_ref[jnp.maximum(k - 1, 0)] != t)
    last = jnp.logical_or(k == last_k,
                          tile_ref[jnp.minimum(k + 1, last_k)] != t)
    ident = _IDENT[op]
    fold = _FOLD[op]

    @pl.when(first)
    def _init():
        acc[...] = jnp.full(acc.shape, ident, jnp.float32)

    @pl.when(code_ref[k] % 2 == 1)
    def _compute():
        cols = lax.broadcasted_iota(jnp.int32, (tile_n, LANES), 0) + t * tile_n
        for f in range(n_feat):
            def group(g, a, f=f):
                r0 = pl.multiple_of(g * 8, 8)
                ids8 = ids_ref[pl.ds(r0, 8), :]
                vals8 = val_ref[f, pl.ds(r0, 8), :]
                for i in range(8):
                    hit = ids8[i:i + 1, :] == cols
                    a = fold(a, jnp.where(hit, vals8[i:i + 1, :], ident))
                return a

            acc[f] = lax.fori_loop(0, rows // 8, group, acc[f])

    @pl.when(last)
    def _finalize():
        for f in range(n_feat):
            # (tile_n, 128) -> (128, tile_n): the lane reduction becomes a
            # sublane reduction whose result is already a lane-major row.
            red = acc[f].T
            if op == "sum":
                res = jnp.sum(red, axis=0, keepdims=True)
            else:
                res = (jnp.max if op == "max" else jnp.min)(
                    red, axis=0, keepdims=True
                )
                res = jnp.where(res == ident, 0.0, res)
            out_ref[f:f + 1, :] = res.astype(out_ref.dtype)


def segment_combine_pallas(
    values: jax.Array,
    segment_ids: jax.Array,
    n_segments: int,
    op: str = "sum",
    *,
    edge_active: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """``values`` [E, F] (f32 or bf16) combined by sorted ``segment_ids``
    [E] into [n_segments, F]; negative ids are padding.  Segments with no
    row read 0.  The accumulation is f32 and the result takes the payload
    dtype."""

    E, F = values.shape
    if E == 0 or n_segments == 0:
        return jnp.zeros((n_segments, F), values.dtype)
    if edge_active is not None:
        segment_ids = jnp.where(edge_active, segment_ids, -1)
    rows, tile_n = _tiling(E, n_segments)
    block_e = rows * LANES
    n_blocks = _cdiv(E, block_e)
    pad_e = n_blocks * block_e - E
    n_tiles = _cdiv(n_segments, tile_n)
    n_feat = F if F <= F_GROUP else F_GROUP
    f_pad = _cdiv(F, n_feat) * n_feat

    ids2 = jnp.pad(segment_ids.astype(jnp.int32), (0, pad_e),
                   constant_values=-1).reshape(n_blocks * rows, LANES)
    # Feature-major, lane-dense payload planes: [F, rows, 128].
    vals3 = jnp.pad(values.astype(jnp.float32).T,
                    ((0, f_pad - F), (0, pad_e)))
    vals3 = vals3.reshape(f_pad, n_blocks * rows, LANES)
    tile, code = _work_list(ids2, n_blocks, rows, n_tiles, tile_n)

    kernel = functools.partial(
        _kernel, op=op, tile_n=tile_n, rows=rows, n_feat=n_feat
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(f_pad // n_feat, n_blocks + n_tiles),
        in_specs=[
            pl.BlockSpec((rows, LANES),
                         lambda g, k, tile, code: (code[k] // 2, 0)),
            pl.BlockSpec((n_feat, rows, LANES),
                         lambda g, k, tile, code: (g, code[k] // 2, 0)),
        ],
        out_specs=pl.BlockSpec((n_feat, tile_n),
                               lambda g, k, tile, code: (g, tile[k])),
        scratch_shapes=[pltpu.VMEM((n_feat, tile_n, LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((f_pad, n_tiles * tile_n),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="segment_combine",
    )(tile, code, ids2, vals3)
    return out[:F, :n_segments].T.astype(values.dtype)
