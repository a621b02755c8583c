"""Property suite: row-table operators vs NumPy set-semantics oracles.

Random relations (including empty, duplicate-heavy, and cap-overflow
inputs) are pushed through the executor's row-table operator kernels —
``_join_rows`` / ``_antijoin_rows`` / ``_project_rows`` / ``_groupby_rows``
— and the surviving rows are compared against independent NumPy/set
oracles.  Runs under real ``hypothesis`` when installed, else the
deterministic ``tests/_hypothesis_compat`` replay shim.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal images: deterministic fallback shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import algebra
from repro.core.executor import (
    _antijoin_rows,
    _Ctx,
    _groupby_rows,
    _join_rows,
    _project_rows,
    _Rows,
)
from repro.core.physical import join_row_codes, sort_row_codes

CAP = 64


def _ctx(n, row_cap=256):
    return _Ctx(
        program=None, n=n, sigs={}, relations={}, state={}, views={},
        materialized={}, connectors={}, j=jnp.int32(0),
        row_cap=row_cap,
    )


def _mk_rows(dims, tuples, rng, cap=CAP, vals=None):
    """Build a padded _Rows slab from a tuple set, with valid rows strewn
    across random slots (padding interleaved, not suffix-only)."""

    k = len(dims)
    rows = np.zeros((cap, max(k, 1))[:1] + (k,), np.int32)
    valid = np.zeros(cap, bool)
    slots = rng.permutation(cap)[: len(tuples)]
    cols = {c: np.zeros(cap, np.float32) for c in (vals or {})}
    for slot, t in zip(slots, tuples):
        rows[slot] = t
        valid[slot] = True
        for c in cols:
            cols[c][slot] = vals[c][t]
    return _Rows(
        tuple(dims), jnp.asarray(rows), jnp.asarray(valid),
        {c: jnp.asarray(v) for c, v in cols.items()},
    )


def _out_tuples(rows):
    ids = np.asarray(rows.ids)
    valid = np.asarray(rows.valid)
    return set(map(tuple, ids[valid].tolist()))


def _rand_rel(rng, n, k, m):
    if m == 0:
        return set()
    return set(map(tuple, rng.integers(0, n, (m, k)).tolist()))


@settings(deadline=None)
@given(seed=st.integers(0, 31), n=st.sampled_from([4, 8, 16]),
       lm=st.sampled_from([0, 3, 20]), rm=st.sampled_from([0, 5, 20]))
def test_join_rows_matches_set_oracle(seed, n, lm, rm):
    rng = np.random.default_rng(seed)
    left = _rand_rel(rng, n, 2, lm)   # (X, Y)
    right = _rand_rel(rng, n, 2, rm)  # (Y, Z)
    ctx = _ctx(n)
    out = _join_rows(
        _mk_rows(("X", "Y"), sorted(left), rng),
        _mk_rows(("Y", "Z"), sorted(right), rng),
        keys=("Y",), ctx=ctx,
    )
    oracle = {(x, y, z) for (x, y) in left for (y2, z) in right if y == y2}
    assert out.dims == ("X", "Y", "Z")
    assert _out_tuples(out) == oracle
    assert not any(bool(f) for f in ctx.overflow)


@settings(deadline=None)
@given(seed=st.integers(0, 31), n=st.sampled_from([4, 8, 16]),
       lm=st.sampled_from([0, 4, 24]), rm=st.sampled_from([0, 4, 24]))
def test_antijoin_rows_matches_set_difference(seed, n, lm, rm):
    rng = np.random.default_rng(seed)
    left = _rand_rel(rng, n, 2, lm)   # (X, Y)
    right = {t[:1] for t in _rand_rel(rng, n, 1, rm)}  # (Y,)
    ctx = _ctx(n)
    out = _antijoin_rows(
        _mk_rows(("X", "Y"), sorted(left), rng),
        _mk_rows(("Y",), sorted(right), rng),
        keys=("Y",), ctx=ctx,
    )
    oracle = {(x, y) for (x, y) in left if (y,) not in right}
    assert _out_tuples(out) == oracle


@settings(deadline=None)
@given(seed=st.integers(0, 31), n=st.sampled_from([4, 8, 16]),
       m=st.sampled_from([0, 6, 32]))
def test_project_rows_dedupes_dropped_dims(seed, n, m):
    # Duplicate-heavy by construction: many (X, Y) rows collapse onto the
    # same X once Y is projected away.
    rng = np.random.default_rng(seed)
    rel = _rand_rel(rng, n, 2, m)
    ctx = _ctx(n)
    out = _project_rows(
        algebra.Project(("X",), None),
        _mk_rows(("X", "Y"), sorted(rel), rng), ctx,
    )
    assert out.dims == ("X",)
    assert _out_tuples(out) == {(x,) for (x, y) in rel}


@settings(deadline=None)
@given(seed=st.integers(0, 15), agg=st.sampled_from(["sum", "min", "max"]),
       m=st.sampled_from([0, 5, 40]), big=st.booleans())
def test_groupby_rows_matches_numpy_oracle(seed, agg, m, big):
    # big=True pushes n**k past the grid-lowering threshold so the
    # segmented sorted-combine path runs; big=False takes the dense
    # grid-reduce lowering.  Both must match the oracle.
    n = 2048 if big else 16
    rng = np.random.default_rng(seed)
    rel = sorted(_rand_rel(rng, n, 2, m))
    vals = {"V": {t: float(np.float32(rng.random())) for t in rel}}
    ctx = _ctx(n)
    out = _groupby_rows(
        algebra.GroupBy(None, ("X",), agg, "V", "acc"),
        _mk_rows(("X", "Y"), rel, rng, vals=vals), ctx,
    )
    combine = {"sum": lambda a: float(np.sum(np.asarray(a, np.float32))),
               "min": min, "max": max}[agg]
    oracle = {}
    for (x, y) in rel:
        oracle.setdefault(x, []).append(vals["V"][(x, y)])
    oracle = {x: combine(vs) for x, vs in oracle.items()}
    got_ids = np.asarray(out.ids)[np.asarray(out.valid)][:, 0]
    got_vals = np.asarray(out.cols["acc"])[np.asarray(out.valid)]
    assert set(got_ids.tolist()) == set(oracle)
    for x, v in zip(got_ids.tolist(), got_vals.tolist()):
        assert abs(v - oracle[x]) <= 1e-6 * max(1.0, abs(oracle[x])), (x, agg)


def test_join_rows_flags_pair_expansion_overflow():
    # 16 x 16 matching pairs = 256 output rows into a 64-slot intermediate:
    # the traced overflow flag must trip (the executor then falls back to
    # dense storage losslessly; tested end-to-end in test_rowtable.py).
    rng = np.random.default_rng(0)
    n = 32
    left = {(x, 0) for x in range(16)}
    right = {(0, z) for z in range(16)}
    ctx = _ctx(n, row_cap=64)
    _join_rows(
        _mk_rows(("X", "Y"), sorted(left), rng),
        _mk_rows(("Y", "Z"), sorted(right), rng),
        keys=("Y",), ctx=ctx,
    )
    assert any(bool(f) for f in ctx.overflow)


def test_join_rows_residual_value_equality():
    # A join key living in a value column on one side: the structural code
    # join cannot see it, so the residual filter must apply it.
    rng = np.random.default_rng(3)
    n = 8
    left = _mk_rows(("X",), [(1,), (2,)], rng,
                    vals={"W": {(1,): 5.0, (2,): 6.0}})
    right = _mk_rows(("W",), [(5,), (7,)], rng)
    # "W" is a value column on the left but a dim on the right: no shared
    # dims, so the structural code join degenerates to a cross product and
    # the residual filter must enforce left.W == right.W.
    out = _join_rows(left, right, keys=("W",), ctx=_ctx(n))
    valid = np.asarray(out.valid)
    ids = np.asarray(out.ids)[valid]
    assert set(map(tuple, ids.tolist())) == {(1, 5)}


def _join_row_codes_by_search(l_codes, l_valid, r_codes, r_valid, out_cap):
    """The pair expansion as a binary search per slot: each of the
    ``out_cap`` slots searches the run ends ``offs`` for its left row and
    gathers that row's offset, start and count."""

    cap_l, cap_r = l_codes.shape[0], r_codes.shape[0]
    perm_r, r_skey, r_nv = sort_row_codes(r_codes, r_valid)
    start = jnp.searchsorted(r_skey, l_codes, side="left").astype(jnp.int32)
    end = jnp.searchsorted(r_skey, l_codes, side="right").astype(jnp.int32)
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


_SENTINEL = 0xFFFFFFFF


def _pair_case(name):
    """``(l_codes, l_valid, r_codes, r_valid, out_cap, expect)``; ``expect``
    holds what the case is built to show: the overflow flag and the number
    of valid slots."""

    rng = np.random.default_rng(sum(map(ord, name)))
    u32 = lambda a: np.asarray(a, np.uint32)  # noqa: E731
    if name == "no_valid_left":
        return (u32(rng.integers(0, 4, 8)), np.zeros(8, bool),
                u32(rng.integers(0, 4, 12)), np.ones(12, bool), 16,
                dict(overflow=False, pairs=0))
    if name == "no_valid_right":
        return (u32(rng.integers(0, 4, 8)), np.ones(8, bool),
                u32(rng.integers(0, 4, 12)), np.zeros(12, bool), 16,
                dict(overflow=False, pairs=0))
    if name == "no_key_matches":
        return (u32(np.arange(8)), np.ones(8, bool),
                u32(np.arange(100, 112)), np.ones(12, bool), 16,
                dict(overflow=False, pairs=0))
    if name == "one_left_row_overflows":
        lc = u32([5, 3, 7, 1])
        return (lc, np.ones(4, bool), u32(np.full(20, 3)), np.ones(20, bool),
                8, dict(overflow=True, pairs=8))
    if name == "total_equals_cap":
        lc = u32([0, 1, 2, 3, 0])
        lv = np.array([True, True, True, True, False])
        rc = u32([3] * 8 + [1] * 5 + [0] * 3 + [9] * 4)
        rv = np.ones(20, bool)
        return lc, lv, rc, rv, 16, dict(overflow=False, pairs=16)
    if name == "duplicate_right_codes":
        lc = u32(rng.integers(0, 5, 24))
        lv = rng.random(24) > 0.2
        rc = u32(rng.integers(0, 3, 40))
        rv = rng.random(40) > 0.2
        return lc, lv, rc, rv, 256, dict(overflow=False, pairs=None)
    if name == "one_left_row":
        return (u32([2]), np.ones(1, bool), u32([2, 0, 2, 2, 1, 2]),
                np.array([True, True, False, True, True, True]), 8,
                dict(overflow=False, pairs=3))
    if name == "sentinel_code_row":
        # domain**k == 2**32: a valid row may carry the code invalid rows
        # sort under; it must match valid right rows only.
        lc = u32([_SENTINEL, 4, _SENTINEL, 0])
        lv = np.array([True, True, False, True])
        rc = u32([_SENTINEL, 4, 0, _SENTINEL, 7, 4])
        rv = np.array([True, True, False, False, False, True])
        return lc, lv, rc, rv, 16, dict(overflow=False, pairs=3)
    if name == "random_overflowing":
        lc = u32(rng.integers(0, 6, 32))
        lv = rng.random(32) > 0.2
        rc = u32(rng.integers(0, 6, 48))
        rv = rng.random(48) > 0.2
        return lc, lv, rc, rv, 64, dict(overflow=True, pairs=64)
    raise KeyError(name)


@pytest.mark.parametrize("case", [
    "no_valid_left", "no_valid_right", "no_key_matches",
    "one_left_row_overflows", "total_equals_cap", "duplicate_right_codes",
    "one_left_row", "sentinel_code_row", "random_overflowing",
])
def test_join_row_codes_matches_per_slot_search(case):
    lc, lv, rc, rv, out_cap, expect = _pair_case(case)
    got = jax.jit(join_row_codes, static_argnums=4)(lc, lv, rc, rv, out_cap)
    want = jax.jit(_join_row_codes_by_search, static_argnums=4)(
        lc, lv, rc, rv, out_cap)
    for name, g, w in zip(("li", "ri", "valid", "overflow"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert bool(got[3]) == expect["overflow"]
    if expect["pairs"] is not None:
        assert int(np.asarray(got[2]).sum()) == expect["pairs"]


def _primitives(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.append(eqn.primitive.name)
        for v in eqn.params.values():
            for x in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(x, jcore.ClosedJaxpr):
                    _primitives(x.jaxpr, acc)
                elif isinstance(x, jcore.Jaxpr):
                    _primitives(x, acc)
    return acc


def test_join_row_codes_loops_only_in_key_searches():
    # Each binary search is one loop (JAX traces its fixed trip count as
    # ``scan``; a ``while`` would count the same): the two searches of the
    # left keys in the sorted right keys, and none over the pair slots.
    u32, b = jnp.zeros((32,), jnp.uint32), jnp.zeros((32,), jnp.bool_)
    closed = jax.make_jaxpr(
        lambda lc, lv, rc, rv: join_row_codes(lc, lv, rc, rv, 256)
    )(u32, b, jnp.zeros((48,), jnp.uint32), jnp.zeros((48,), jnp.bool_))
    prims = _primitives(closed.jaxpr, [])
    assert prims.count("while") + prims.count("scan") <= 2, prims
