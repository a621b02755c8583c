"""``correct`` holds for the program as it is and comes out false when the
timed path is broken underneath: a step that returns its state unchanged,
half of the edges left out, an answer altered where it is produced.  (A
one-chip cell has no exchange between chips to leave out.)"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from tiny_root import make_root, run_tiny

CELLS = ["pagerank.graph500-22", "ppr-rowtable.graph500-20"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("cell", CELLS)
def test_the_program_as_it_is_is_correct(root, cell):
    out = run_tiny(root, cell)
    assert out["correct"] and out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    assert set(out["metrics"]) == {"iteration_s", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.core import fixpoint

    orig_device, orig_host = fixpoint.device_fixpoint, \
        fixpoint.HostFixpointDriver.run

    def device(body, converged, init, max_iters, donate=True):
        return orig_device(lambda s, j: s, converged, init, max_iters)

    def host(self, init_state, start_iter=0):
        self.step = lambda s, j: s
        self.select_step = None
        return orig_host(self, init_state, start_iter)

    import repro.core.pregel as pregel

    monkeypatch.setattr(pregel, "device_fixpoint", device)
    monkeypatch.setattr(fixpoint.HostFixpointDriver, "run", host)


def _half_the_edges(monkeypatch):
    import repro.core.executor as executor
    import repro.core.pregel as pregel

    orig_pregel, orig_program = pregel.compile_pregel, \
        executor.compile_program

    def half_graph(prog, graph, **kw):
        m = graph.n_edges // 2
        return orig_pregel(prog, dataclasses.replace(
            graph, src=graph.src[:m], dst=graph.dst[:m]), **kw)

    def half_table(program, relations, **kw):
        edge = relations["edge"]
        m = edge.rows.shape[0] // 2
        rels = dict(relations, edge=dataclasses.replace(
            edge, rows=edge.rows[:m]))
        return orig_program(program, rels, **kw)

    monkeypatch.setattr(pregel, "compile_pregel", half_graph)
    monkeypatch.setattr(executor, "compile_program", half_table)


def _answer_altered(monkeypatch):
    import repro.core.executor as executor
    import repro.core.pregel as pregel

    orig_pregel, orig_generic = pregel.PregelExecutable.run, \
        executor.GenericExecutable.run

    def pregel_run(self, *a, **kw):
        res = orig_pregel(self, *a, **kw)
        state, active = res.state
        return dataclasses.replace(
            res, state=(state.at[7, 0].multiply(1.01), active))

    def generic_run(self, *a, **kw):
        res = orig_generic(self, *a, **kw)
        rank = res.state["rank"]
        k, v = next(iter(rank.values.items()))
        i = int(np.flatnonzero(np.asarray(rank.present))[0])
        rank = dataclasses.replace(
            rank, values={k: jnp.asarray(v).at[i].multiply(1.01)})
        return dataclasses.replace(res, state=dict(res.state, rank=rank))

    monkeypatch.setattr(pregel.PregelExecutable, "run", pregel_run)
    monkeypatch.setattr(executor.GenericExecutable, "run", generic_run)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_edges,
                                   _answer_altered],
                         ids=["state-unchanged", "half-the-edges",
                              "answer-altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_tiny(root, cell)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
