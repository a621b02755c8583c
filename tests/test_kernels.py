"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal images: deterministic fallback shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.segment_combine.ops import segment_combine
from repro.kernels.segment_combine.ref import segment_combine_reference

RNG = np.random.default_rng(0)


def _mk(B, H, KH, Sq, Skv, D, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, Sq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, KH, Skv, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, KH, Skv, D)), dtype)
    return q, k, v


FLASH_SWEEP = [
    # B, H, KH, Sq, Skv, D, causal, window, dtype, tol
    (1, 2, 2, 128, 128, 64, True, None, jnp.float32, 2e-6),
    (2, 4, 2, 128, 128, 64, True, None, jnp.float32, 2e-6),   # GQA
    (1, 4, 1, 64, 64, 32, False, None, jnp.float32, 2e-6),    # MQA bidir
    (1, 2, 2, 128, 128, 64, True, 64, jnp.float32, 2e-6),     # SWA
    (1, 2, 2, 256, 256, 64, True, 32, jnp.float32, 2e-6),     # narrow SWA
    (1, 2, 1, 64, 256, 64, True, None, jnp.float32, 2e-6),    # Sq < Skv
    (1, 2, 2, 128, 128, 128, True, None, jnp.float32, 2e-6),  # D=128
    (1, 2, 2, 128, 128, 64, True, None, jnp.bfloat16, 3e-2),
    (1, 8, 2, 64, 64, 32, True, None, jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize(
    "B,H,KH,Sq,Skv,D,causal,window,dtype,tol", FLASH_SWEEP
)
def test_flash_forward_matches_reference(B, H, KH, Sq, Skv, D, causal,
                                         window, dtype, tol):
    q, k, v = _mk(B, H, KH, Sq, Skv, D, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


@pytest.mark.parametrize(
    "B,H,KH,Sq,Skv,D,causal,window",
    [(1, 2, 2, 128, 128, 64, True, None),
     (1, 4, 2, 128, 128, 64, True, None),
     (1, 2, 2, 128, 128, 64, True, 64),
     (2, 2, 1, 64, 64, 32, False, None)],
)
def test_flash_backward_matches_reference(B, H, KH, Sq, Skv, D, causal,
                                          window):
    q, k, v = _mk(B, H, KH, Sq, Skv, D, jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, window=window, interpret=True,
            block_q=64, block_k=64,
        ) * jnp.cos(jnp.arange(D, dtype=jnp.float32)))

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, k, v, causal=causal, window=window,
        ) * jnp.cos(jnp.arange(D, dtype=jnp.float32)))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_fully_masked_rows_are_zero():
    # window=1 + Sq==Skv: row 0 sees only itself; bidirectional masked case
    q, k, v = _mk(1, 2, 2, 64, 64, 32, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=1, interpret=True,
                          block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


SEG_SWEEP = [
    (1000, 8, 64, "sum"), (513, 16, 200, "sum"), (2048, 32, 256, "sum"),
    (256, 4, 32, "max"), (777, 8, 130, "min"), (64, 128, 16, "sum"),
]


@pytest.mark.parametrize("E,F,N,op", SEG_SWEEP)
def test_segment_combine_matches_reference(E, F, N, op):
    ids = np.sort(RNG.integers(0, N, size=E - 3)).astype(np.int32)
    ids = np.concatenate([ids, np.full(3, -1, np.int32)])  # padding rows
    vals = RNG.normal(size=(E, F)).astype(np.float32)
    out = segment_combine(jnp.asarray(vals), jnp.asarray(ids), N, op,
                          interpret=True)
    ref = segment_combine_reference(jnp.asarray(vals), jnp.asarray(ids), N, op)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_kernel_eligible_accepts_bf16_rejects_wider():
    # bf16 payloads must ride the Pallas kernel (f32 accumulation, cast
    # back on output) instead of silently skipping to the XLA fallback;
    # f64/int payloads would be narrowed by the f32 accumulator and stay
    # ineligible.
    from repro.kernels.segment_combine.ops import kernel_eligible

    bf16 = jnp.zeros((8, 2), jnp.bfloat16)
    f32 = jnp.zeros((8, 2), jnp.float32)
    i32 = jnp.zeros((8, 2), jnp.int32)
    assert kernel_eligible(bf16, True)
    assert kernel_eligible(f32, True)
    assert not kernel_eligible(i32, True)
    if jax.default_backend() != "tpu":
        # off-TPU without interpret mode there is no kernel to run at all
        assert not kernel_eligible(bf16, None)


@pytest.mark.parametrize("op", ["sum", "max", "min"])
def test_segment_combine_bf16_payload_matches_f32_reference(op):
    # The kernel accumulates bf16 payloads in f32 and casts back, so the
    # result must agree with the f32 reference to bf16 resolution.
    E, F, N = 600, 4, 40
    rng = np.random.default_rng(9)
    ids = jnp.asarray(np.sort(rng.integers(0, N, E)).astype(np.int32))
    vals32 = rng.normal(size=(E, F)).astype(np.float32)
    out = segment_combine(jnp.asarray(vals32, jnp.bfloat16), ids, N, op,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = segment_combine_reference(
        jnp.asarray(vals32, jnp.bfloat16).astype(jnp.float32), ids, N, op)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=25, deadline=None)
@given(
    n_seg=st.integers(2, 40),
    n_rows=st.integers(1, 200),
    f=st.sampled_from([1, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_segment_combine_property_sorted_sum(n_seg, n_rows, f, seed):
    """Kernel == oracle == dense matmul for any sorted id multiset."""

    rng = np.random.default_rng(seed)
    ids = np.sort(rng.integers(0, n_seg, size=n_rows)).astype(np.int32)
    vals = rng.normal(size=(n_rows, f)).astype(np.float32)
    out = segment_combine(jnp.asarray(vals), jnp.asarray(ids), n_seg, "sum",
                          interpret=True)
    dense = np.zeros((n_seg, f), np.float32)
    for i, s in enumerate(ids):
        dense[s] += vals[i]
    np.testing.assert_allclose(np.asarray(out), dense, atol=1e-4)


@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("layout", ["trailing", "interleaved"])
def test_segment_combine_dead_blocks_cover_every_tile(op, layout):
    """Edge blocks with no live row (trailing padding, or a masked middle
    of the frontier) must neither crowd tiles out of the kernel's work
    list nor leave a tile unwritten: 8 blocks against 32 output tiles."""

    rng = np.random.default_rng(11)
    E, N = 8 * 1024, 4096
    vals = rng.normal(size=(E, 3)).astype(np.float32)
    if layout == "trailing":
        ids = np.full(E, -1, np.int32)
        ids[:900] = np.sort(rng.integers(0, 100, 900))
        act = None
    else:
        ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
        act = np.zeros(E, bool)
        act[:500] = act[-500:] = True
        act = jnp.asarray(act)
    out = segment_combine(jnp.asarray(vals), jnp.asarray(ids), N, op,
                          edge_active=act, interpret=True)
    ref = segment_combine_reference(jnp.asarray(vals), jnp.asarray(ids), N,
                                    op, edge_active=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
