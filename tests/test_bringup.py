"""What running on the chip needs from the engine: device kinds, steps that
take their arrays as arguments, a fixed compile cache, EDB slabs sized to
their rows, and compile errors that are not retried."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algebra
from repro.core.executor import _Ctx, _pair_cap
from repro.core.fixpoint import DriverConfig, HostFixpointDriver, jit_hoisted
from repro.core.hardware import (
    DEVICE_KINDS, MeshSpec, TPU_V5E, hardware_for,
)
from repro.core.planner import plan_program
from repro.launch import compile_cache


@pytest.mark.parametrize("kind", sorted(DEVICE_KINDS))
def test_known_device_kinds_map_to_v5e(kind):
    assert hardware_for(kind) is TPU_V5E


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "TPU v5e", "cpu",
                                  ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(ValueError, match="no hardware spec"):
        hardware_for(kind)


def test_jit_hoisted_passes_closed_over_arrays_as_arguments():
    big = jnp.arange(1 << 16, dtype=jnp.int32)
    step = jit_hoisted(lambda x, j: (jnp.sort(big)[:4] + x, j + 1))
    out = step(jnp.int32(1), jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(out[0]), [1, 2, 3, 4])
    assert int(out[1]) == 1
    text = step.lower(jnp.int32(1), jnp.int32(0)).as_text()
    # the array is a parameter of the program, not a 256 KiB constant
    assert "tensor<65536xi32>" in text.split("func.func public @main")[1] \
        .split(")")[0]
    assert len(text) < 20_000


def test_jit_hoisted_reuses_one_trace_per_signature():
    calls = []
    table = jnp.ones((8,), jnp.float32)

    def f(x):
        calls.append(1)
        return x * table

    step = jit_hoisted(f)
    for _ in range(3):
        step(jnp.ones((8,), jnp.float32))
    out = step(jnp.ones((8,), jnp.float32) * 2)
    assert len(calls) == 1  # one trace for the one input signature
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 2.0))
    step(jnp.ones((4,), jnp.float32)[:, None] * jnp.ones((1, 8)))
    assert len(calls) == 2  # a new shape traces again


def test_jit_hoisted_compiled_returns_the_executables_that_ran():
    compiles = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    table = jnp.arange(1 << 12, dtype=jnp.float32)
    step = jit_hoisted(lambda x, j: (jnp.sort(table * x.sum())[:2], j + 1))
    step(jnp.float32(-1.0), jnp.int32(0))
    step(jax.device_put(jnp.ones((3,)), jax.devices()[0]), 1)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        compiled = step.compiled()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(compiled) == 2 and compiles == []
    assert all("sort" in c.as_text() for c in compiled)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_repo_directory(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.DEFAULT_CACHE_DIR)
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("rows", [3_000_000, 67_108_703])
def test_edb_row_table_holds_every_row(rows):
    # A RowRelation EDB's count is exact: its slab holds every row, however
    # large, while the shared intermediate cap keeps its 4M-row ceiling
    # (only joins that read the slab grow to it: see the test below).
    plan = plan_program(
        (("rank",),), (), 1 << 22, MeshSpec((("data", 1),)),
        predicates={"edge": (2, float(rows)), "rank": (1, 4e6)},
        storage={"edge": "row-table"}, edb=("edge",),
    )
    assert plan.row_caps["edge"] >= rows
    assert plan.row_cap == 1 << 22
    assert "chunking" not in " ".join(plan.notes)


_EDGE = algebra.ScanEDB("edge", ("Y", "X"))
_RANK = algebra.ScanState("rank", ("Y", "R"))
_TC = algebra.ScanState("tc", ("X", "Z"))


@pytest.mark.parametrize("op, lift, want", [
    (algebra.Join(_RANK, _EDGE, ("Y",)), True, 1 << 12),
    (algebra.Join(algebra.Join(_RANK, _EDGE, ("Y",)), _TC, ("X",)), True,
     1 << 12),
    (algebra.Join(_RANK, _TC, ()), True, 64),
    (algebra.Join(_RANK, _EDGE, ("Y",)), False, 64),
])
def test_joins_reading_an_edb_slab_get_a_pair_per_row(op, lift, want):
    # One pair slot per EDB slab row for a join that reads the slab; other
    # joins, and every join under a pinned row_cap, keep the plan's cap.
    ctx = _Ctx(
        program=None, n=1 << 10, sigs={}, relations={}, state={}, views={},
        materialized={}, connectors={}, j=jnp.int32(0), row_cap=64,
        row_edb={"edge": {"ids": np.zeros((1 << 12, 2), np.int32)}},
        edb_join_rows=lift,
    )
    assert _pair_cap(op, ctx) == want


def test_derived_row_caps_stay_bounded():
    # Estimates of derived predicates keep their 1M-row ceiling.
    plan = plan_program(
        (("tc",),), (), 1 << 16, MeshSpec((("data", 1),)),
        predicates={"tc": (2, 1e9)}, storage={"tc": "row-table"},
    )
    assert plan.row_caps["tc"] == 1 << 20


@pytest.mark.parametrize("exc", [
    ValueError("bad shapes"),
    TypeError("not an array"),
    NotImplementedError("no lowering"),
])
def test_compile_errors_surface_without_restore(exc):
    restores = []

    def step(state, j):
        raise exc

    driver = HostFixpointDriver(
        step=step, converged=lambda a, b: False,
        config=DriverConfig(max_iters=4, max_restarts=3),
        restore=lambda: restores.append(1) or (0, 0),
    )
    with pytest.raises(type(exc)):
        driver.run(0)
    assert restores == [] and driver.restarts == 0


def test_device_failures_still_restore():
    restores = []
    fired = []

    def step(state, j):
        if not fired:
            fired.append(j)
            raise RuntimeError("injected device failure")
        return state + 1

    driver = HostFixpointDriver(
        step=step, converged=lambda a, b: False,
        config=DriverConfig(max_iters=3, max_restarts=3),
        restore=lambda: restores.append(1) or (0, 0),
    )
    res = driver.run(0)
    assert restores == [1] and res.restarts == 1 and res.state == 3
