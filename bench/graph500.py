"""Graph500 Kronecker graphs made on the device from a seed.

The Graph500 specification (graph500.org, "Benchmark Specification",
section 3) draws each of ``edgefactor << SCALE`` edge tuples
independently: at each of SCALE levels the edge falls into one quadrant of
the adjacency matrix with probabilities A, B, C and D = 1 - A - B - C,
which sets one bit of its start and one bit of its end vertex.  The vertex
labels are then permuted at random.  Duplicate edges and self-loops are
kept: the edge list is a multigraph.  Each edge is undirected, so the
list holds every tuple in both directions, as the specification's kernels
and LDBC Graphalytics' graph500 datasets read it.

A configuration with a ``dataset_seed`` stands for one published dataset:
its edges are one fixed draw, and the run's seed only permutes the vertex
labels, so every seed gives the same graph in another order.

The reference generator makes two uniform draws per level; one draw split
into four intervals has the same joint law, so each level here costs one
uniform per edge.  Edges are i.i.d., so the specification's shuffle of the
edge list changes nothing in law and is not done.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def prng_key(seed: int) -> jax.Array:
    """A key that depends on every bit of a non-negative ``seed`` of up to
    64 bits (``jax.random.key`` keeps only the low 32 without x64)."""

    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a non-negative 64-bit integer")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(jax.jit, static_argnames=("scale", "edgefactor", "a", "b",
                                             "c", "permute"))
def kronecker(key, label_key, *, scale: int, edgefactor: int, a: float,
              b: float, c: float, permute: bool):
    """``(src, dst)`` int32 arrays of the ``edgefactor << scale`` edges
    drawn from ``key`` followed by their reverses; ``label_key`` permutes
    the labels."""

    m = edgefactor << scale
    ab, abc = a + b, a + b + c

    def level(bit, sd):
        s, d = sd
        u = jax.random.uniform(jax.random.fold_in(key, bit), (m,))
        s_bit = u >= ab
        d_bit = ((u >= a) & (u < ab)) | (u >= abc)
        return (s | (s_bit.astype(jnp.int32) << bit),
                d | (d_bit.astype(jnp.int32) << bit))

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    if permute:
        perm = _labels(label_key, 1 << scale)
        src, dst = perm[src], perm[dst]
    return jnp.concatenate([src, dst]), jnp.concatenate([dst, src])


def _labels(label_key, n: int):
    return jax.random.permutation(label_key, n).astype(jnp.int32)


def _keys(cfg: dict, seed: int):
    key = prng_key(seed)
    label_key = jax.random.fold_in(key, cfg["scale"])
    if "dataset_seed" in cfg:
        key = prng_key(cfg["dataset_seed"])
    return key, label_key


def make_edges(cfg: dict, seed: int):
    """The configuration's edge list on the device: ``(n, src, dst)``."""

    if cfg["generator"] != "graph500_kronecker":
        raise ValueError(f"unknown generator {cfg['generator']!r}")
    src, dst = kronecker(*_keys(cfg, seed), scale=cfg["scale"],
                         edgefactor=cfg["edgefactor"], a=cfg["a"],
                         b=cfg["b"], c=cfg["c"],
                         permute=cfg["permute_labels"])
    return 1 << cfg["scale"], src, dst


def labels(cfg: dict, seed: int) -> np.ndarray:
    """The label each vertex of the unpermuted draw gets in ``make_edges``
    for this seed."""

    n = 1 << cfg["scale"]
    if not cfg["permute_labels"]:
        return np.arange(n, dtype=np.int32)
    return np.asarray(jax.jit(_labels, static_argnums=1)(
        _keys(cfg, seed)[1], n))


@functools.partial(jax.jit, static_argnames=("n",))
def out_degree(src, n: int):
    """float32 out-degree of each vertex."""

    return jax.ops.segment_sum(jnp.ones_like(src, jnp.float32), src, n)


@jax.jit
def _sorted_unique_mask(src, dst):
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    first = jnp.ones((1,), bool)
    new = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    return src, dst, jnp.concatenate([first, new])


def unique_rows(src, dst) -> np.ndarray:
    """The edge set as host rows ``int32 [count, 2]``, duplicates dropped,
    in lexicographic order: sorted on the device, compacted on the host."""

    s, d, keep = (np.asarray(x) for x in _sorted_unique_mask(src, dst))
    return np.stack([s[keep], d[keep]], axis=1)
