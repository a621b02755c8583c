"""The engine names its device work and host phases in the profiler's
trace: every instruction of a row-table step and of a Pregel superstep
carries an operator or stage scope in its HLO ``op_name``; the fixpoint
drivers and the generic executor open host spans (``fixpoint.*``,
``executor.*``); and none of it changes a result."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import Relation, RowRelation, compile_program
from repro.core.fixpoint import HostFixpointDriver, DriverConfig
from repro.core.pregel import Graph, VertexProgram, compile_pregel
from repro.core.serving import personalized_pagerank_program

# Scopes the engine opens (docs/optimizations.md, "Tracing a run").
SCOPES = {
    "scan", "join", "cross", "antijoin", "select", "project", "extend",
    "apply", "groupby", "materialize", "merge", "diff", "overflow",
    "gather", "exchange", "compact", "converged", "sort", "expand", "runs",
    "combine",
}
# Instructions that do no work of their own.
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element"}

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+)[^=]*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(hlo: str):
    """(opcode, full op_name or None, line) of every instruction of the
    entry computation and of the functions and loops it calls; a callee's
    op_names are relative to its call site, so the call's name is put in
    front.  Scalar regions (reducers, comparators) are left out."""

    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        m = _INSTRUCTION.match(line)
        if cur is not None and m:
            name = _OP_NAME.search(line)
            callees = re.findall(r"(?:to_apply|body|condition)=%?([\w.\-]+)",
                                 line)
            comps[cur].append((m.group(1), name and name.group(1), callees,
                               line))
    prefix, todo, out = {entry: ""}, [entry], []
    while todo:
        comp = todo.pop()
        for opcode, name, callees, line in comps[comp]:
            full = None if name is None else "/".join(
                x for x in (prefix[comp], name) if x)
            out.append((opcode, full, line))
            if opcode not in ("call", "while"):
                continue
            for callee in callees:
                if callee not in prefix:
                    prefix[callee] = full if opcode == "call" else \
                        prefix[comp]
                    todo.append(callee)
    return out


def _scopes_of(hlo: str):
    """Every program scope seen, and the instructions that carry none."""

    seen, bare = set(), []
    for opcode, full, line in _op_names(hlo):
        if opcode in PLUMBING or re.search(r"broadcast\(%?constant", line):
            continue
        stack = (full or "").split("/")[:-1]
        scopes = {s for s in stack if s in SCOPES or s.startswith("rule.")}
        seen |= scopes
        if not scopes:
            bare.append(line.strip()[:160])
    return seen, bare


def _row_table_ppr(n=2048, edges=12_000, seed=0):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, edges), rng.integers(0, n, edges)
    rows = np.unique(np.stack([np.r_[src, dst], np.r_[dst, src]], 1),
                     axis=0).astype(np.int32)
    outdeg = np.bincount(rows[:, 0], minlength=n).astype(np.float32)
    rels = {"edge": RowRelation(n=n, key_positions=(0, 1), rows=rows),
            "deg": Relation.from_columns(n, np.arange(n), outdeg),
            "seed": Relation.from_columns(n, np.array([3]),
                                          np.ones(1, np.float32))}
    return compile_program(personalized_pagerank_program(0.85), rels)


def _pagerank(n=64, seed=1, semi_naive=False):
    rng = np.random.default_rng(seed)
    src = np.r_[np.arange(n), rng.integers(0, n, 3 * n)].astype(np.int32)
    dst = np.r_[rng.integers(0, n, n), np.arange(n),
                rng.integers(0, n, 2 * n)].astype(np.int32)
    outdeg = jnp.asarray(np.bincount(src, minlength=n), jnp.float32)
    prog = VertexProgram(
        init_vertex=lambda ids, od: jnp.stack(
            [jnp.full((n,), 1.0 / n), od], axis=1),
        message=lambda j, s, ed: s[:, 0] / jnp.maximum(s[:, 1], 1.0),
        apply=lambda j, s, inbox, got: (
            jnp.stack([0.15 / n + 0.85 * inbox, s[:, 1]], axis=1),
            jnp.ones(s.shape[0], jnp.bool_)),
        combine="sum",
    )
    graph = Graph(n, jnp.asarray(src), jnp.asarray(dst), outdeg)
    return compile_pregel(prog, graph, semi_naive=semi_naive)


def test_every_instruction_of_a_row_table_step_has_a_scope():
    ex = _row_table_ppr()
    assert ex.storage["edge"] == "row-table"
    ex.run(2)
    (step,) = ex._step_cache.values()
    (lowered,) = [run.lower(consts, *specs)
                  for run, consts, _, specs in step._traced.values()]
    seen, bare = _scopes_of(lowered.as_text(dialect="hlo", debug_info=True))
    assert bare == []
    assert {"join", "groupby", "expand", "sort", "merge", "diff"} <= seen
    assert any(s.startswith("rule.") for s in seen)


@pytest.mark.parametrize("sparse", [False, True])
def test_every_instruction_of_a_pregel_superstep_has_a_scope(sparse):
    ex = _pagerank(semi_naive=sparse)
    carry = ex.init()
    step = ex.sparse_superstep(64) if sparse else ex.jitted_superstep
    hlo = step.lower(carry, jnp.int32(0)).as_text(dialect="hlo",
                                                   debug_info=True)
    seen, bare = _scopes_of(hlo)
    assert bare == []
    assert {"gather", "exchange", "apply", "combine"} <= seen
    assert ("compact" in seen) == sparse


def _trace_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [ev for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def _trace(fn, log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _trace_events(log_dir)


def test_each_device_loop_run_traces_its_program_once(tmp_path):
    ex = _pagerank()
    ex.run(3)  # nothing is compiled inside the trace
    _, events = _trace(lambda: [ex.run(3) for _ in range(2)], tmp_path)
    names = [ev.name for ev in events]
    assert names.count("fixpoint.device_loop") == 2
    assert names.count("fixpoint.trace") == 2
    loops = [ev for ev in events if ev.name == "fixpoint.device_loop"]
    for ev in events:
        if ev.name == "fixpoint.trace":
            assert any(lp.start_ns <= ev.start_ns and ev.end_ns <= lp.end_ns
                       for lp in loops)


def test_host_driver_iterations_carry_their_index_and_mode(tmp_path):
    step = jax.jit(lambda s, j: s + 1)
    driver = HostFixpointDriver(
        step=step, converged=lambda prev, new: new >= 100,
        config=DriverConfig(max_iters=3),
        select_step=lambda s, j: (step, "dense"))
    res, events = _trace(lambda: driver.run(jnp.int32(0)), tmp_path)
    assert res.iterations == 3
    iters = [ev for ev in events if ev.name == "fixpoint.iteration"]
    assert sorted(dict(ev.stats)["iteration"] for ev in iters) == [0, 1, 2]
    assert {dict(ev.stats)["mode"] for ev in iters} == {"dense"}
    for name in ("fixpoint.dispatch", "fixpoint.wait", "fixpoint.converged"):
        inner = [ev for ev in events if ev.name == name]
        assert len(inner) == 3
        assert all(any(it.start_ns <= ev.start_ns and ev.end_ns <= it.end_ns
                       for it in iters) for ev in inner)


def _leaves(state):
    """The arrays of a result state: a pytree, or relations by name."""

    if isinstance(state, dict) and all(
            isinstance(r, (Relation, RowRelation)) for r in state.values()):
        return [a for name in sorted(state) for a in (
            getattr(state[name], "present", None),
            getattr(state[name], "rows", None),
            *state[name].values.values()) if a is not None]
    return jax.tree_util.tree_leaves(state)


def _same_result(a, b):
    for f in ("iterations", "converged", "restarts", "modes",
              "phase_iterations", "straggler_events", "remesh_events",
              "storage_fallback"):
        assert getattr(a, f) == getattr(b, f), f
    leaves_a, leaves_b = _leaves(a.state), _leaves(b.state)
    assert len(leaves_a) == len(leaves_b)
    for x, y in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_results_are_the_same_under_the_profiler(tmp_path):
    ppr, pagerank = _row_table_ppr(), _pagerank()
    adaptive = _pagerank(semi_naive=True)
    plain = [ppr.run(3), pagerank.run(3), adaptive.run(5)]
    traced, events = _trace(
        lambda: [ppr.run(3), pagerank.run(3), adaptive.run(5)],
        tmp_path / "on")
    for a, b in zip(plain, traced):
        _same_result(a, b)
    names = {ev.name for ev in events}
    assert {"executor.prelude", "executor.phase_init", "executor.finals",
            "executor.overflow_check", "executor.result"} <= names
