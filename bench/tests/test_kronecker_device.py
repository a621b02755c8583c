"""The device Kronecker generator follows Graph500's quadrant law."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import graph500  # noqa: E402

CFG = dict(generator="graph500_kronecker", scale=6, edgefactor=512,
           a=0.57, b=0.19, c=0.19, permute_labels=False)
M = 512 << 6


def test_each_level_draws_its_quadrant_with_the_published_odds():
    n, src, dst = graph500.make_edges(CFG, 7)
    src, dst = np.asarray(src)[:M], np.asarray(dst)[:M]
    assert n == 64
    assert src.dtype == np.int32 and src.min() >= 0 and src.max() < n
    want = [0.57, 0.19, 0.19, 0.05]
    for bit in range(CFG["scale"]):
        s, d = (src >> bit) & 1, (dst >> bit) & 1
        got = [np.mean((s == i) & (d == j)) for i in (0, 1) for j in (0, 1)]
        # 32768 draws: one standard error is at most 0.0028
        assert np.allclose(got, want, atol=0.012), (bit, got)


def test_permuted_labels_keep_the_degree_sequence():
    _, src, _ = graph500.make_edges(CFG, 7)
    _, psrc, _ = graph500.make_edges(dict(CFG, permute_labels=True), 7)
    deg = np.sort(np.bincount(np.asarray(src), minlength=64))
    pdeg = np.bincount(np.asarray(psrc), minlength=64)
    assert np.array_equal(np.sort(pdeg), deg)
    assert not np.array_equal(pdeg, np.bincount(np.asarray(src),
                                                minlength=64))


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 5, 2**40 + 3])
def test_the_seed_fixes_the_graph_and_every_bit_counts(seed):
    a = np.asarray(graph500.make_edges(CFG, seed)[1])
    b = np.asarray(graph500.make_edges(CFG, seed)[1])
    c = np.asarray(graph500.make_edges(CFG, seed + (1 << 32))[1])
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_unique_rows_is_the_sorted_edge_set():
    _, src, dst = graph500.make_edges(dict(CFG, edgefactor=16), 3)
    rows = graph500.unique_rows(src, dst)
    want = np.unique(np.stack([np.asarray(src), np.asarray(dst)], 1), axis=0)
    assert np.array_equal(rows, want)


def test_an_undirected_graph_holds_each_edge_both_ways():
    _, src, dst = graph500.make_edges(CFG, 11)
    src, dst = np.asarray(src), np.asarray(dst)
    assert src.shape == dst.shape == (2 * M,)
    assert np.array_equal(src[M:], dst[:M])
    assert np.array_equal(dst[M:], src[:M])
    rows = graph500.unique_rows(src, dst)
    assert np.array_equal(np.unique(rows[:, ::-1], axis=0), rows)


def test_a_dataset_is_one_draw_that_the_seed_relabels():
    cfg = dict(CFG, edgefactor=16, permute_labels=True, dataset_seed=5)
    graphs = []
    for seed in (1, 2**33 + 1):
        _, src, dst = graph500.make_edges(cfg, seed)
        inv = np.argsort(graph500.labels(cfg, seed))
        graphs.append((inv[np.asarray(src)], inv[np.asarray(dst)]))
    assert np.array_equal(graphs[0][0], graphs[1][0])
    assert np.array_equal(graphs[0][1], graphs[1][1])
    _, src1, _ = graph500.make_edges(cfg, 1)
    _, src2, _ = graph500.make_edges(cfg, 2)
    assert not np.array_equal(np.asarray(src1), np.asarray(src2))


def test_labels_are_the_permutation_make_edges_applies():
    plain = dict(CFG, edgefactor=16)
    _, src, dst = graph500.make_edges(plain, 9)
    _, psrc, pdst = graph500.make_edges(dict(plain, permute_labels=True), 9)
    perm = graph500.labels(dict(plain, permute_labels=True), 9)
    assert np.array_equal(perm[np.asarray(src)], np.asarray(psrc))
    assert np.array_equal(perm[np.asarray(dst)], np.asarray(pdst))
