"""The Pallas kernels compiled for a described TPU v5e at real widths.

Nothing runs here: each case lowers and compiles one kernel for a chip that
``jax.experimental.topologies`` describes, so the chip's own compiler
raises what it would raise on the device (blocks not aligned to the
tiling, layouts Mosaic cannot lower, scalar or vector memory overruns),
and the compiled program must contain the kernel (``tpu_custom_call``).
The interpret-mode tests in ``test_kernels.py`` check the results.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers must all collect the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_fwd
from repro.kernels.segment_combine.kernel import segment_combine_pallas


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip cannot be read back."""

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("log_edges", [22, 26])
@pytest.mark.parametrize("op,features", [
    ("sum", 1), ("sum", 2), ("min", 1), ("max", 1),
])
def test_segment_combine_compiles_for_v5e(one_chip, log_edges, op,
                                          features):
    # A Graph500 graph of edge factor 16: 2^26 edges is scale 22.
    edges = 1 << log_edges
    values = jax.ShapeDtypeStruct((edges, features), jnp.float32,
                                  sharding=one_chip)
    ids = jax.ShapeDtypeStruct((edges,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda v, i: segment_combine_pallas(v, i, edges // 16, op)
    ).lower(values, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_fwd_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_fwd(q, k, v, causal=True, window=None,
                                  sm_scale=128 ** -0.5)
    ).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()
