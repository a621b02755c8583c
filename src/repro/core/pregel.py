"""Pregel front-end (paper §2.1, Listing 1, Fig. 4).

"Think like a vertex", TPU-native.  The user supplies the Listing-1 UDFs in
vectorized (dense, fixed-shape) form:

* ``init_vertex(ids, vertex_data) -> state``          (rule L1)
* ``message(j, src_state, edge_data) -> payload``     (the message half of
  the ``update`` UDF, evaluated per edge on the *source* shard)
* ``apply(j, state, inbox, aux) -> (new_state, active)`` (the state-update
  half of ``update``; ``active`` is the vote-to-halt bit — rule L7's
  non-null state and the self-activation message of §3.1)
* ``combine`` — a named commutative/associative aggregate over messages
  (rule L3).

The graph is dense-id CSR-ish: ``src``/``dst`` int arrays over edges,
vertices ``[0, N)`` partitioned contiguously over the data axes, edges
partitioned by source vertex so messages are computed from purely local
state (loop-invariant caching: topology never moves — §5.2's
order-of-magnitude argument vs Hadoop).  Optional per-edge attributes
(``Graph.edge_data``, any pytree with leading dim E — weights, labels,
feature rows) ride along on every layout.

This module is a **thin front-end**: it binds the UDFs into the Listing-1
Datalog program, probes the workload statistics, and cost-plans the physical
strategy; the superstep pipeline itself — the Fig.-4 dataflow, the sharded
edge-slab partitioning, the frontier-compacted sparse variants — is
materialized by the unified executor
(:func:`repro.core.executor.build_pregel_steps`), the same engine that runs
arbitrary XY-stratified programs through
:func:`repro.core.executor.compile_program`.

Supersteps run to the Appendix-B.2 fixpoint: no active vertices.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import algebra, stratify
from repro.core.datalog import Program
from repro.core.executor import build_pregel_steps
from repro.core.fixpoint import (
    DriverConfig,
    FixpointResult,
    HostFixpointDriver,
    device_fixpoint,
    jit_hoisted,
)
from repro.core.hardware import MeshSpec, TPU_V5E, HardwareSpec
from repro.core.listings import pregel_program
from repro.core.monoid import get_monoid
from repro.core.physical import scatter_combine
from repro.core.planner import PregelPhysicalPlan, PregelStats, plan_pregel

__all__ = ["Graph", "VertexProgram", "PregelExecutable", "compile_pregel"]


@dataclass
class Graph:
    """Static graph: dense ids, edge list partitioned by source."""

    n_vertices: int
    src: jax.Array            # int32[E] source vertex ids (global)
    dst: jax.Array            # int32[E] destination vertex ids (global)
    vertex_data: Any          # pytree with leading dim N (EDB `data`)
    edge_data: Any = None     # optional pytree with leading dim E

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    def out_degree(self) -> jax.Array:
        return scatter_combine(
            jnp.ones_like(self.src, dtype=jnp.float32),
            self.src, self.n_vertices, "sum",
        )


@dataclass
class VertexProgram:
    """The Listing-1 UDFs in vectorized form."""

    init_vertex: Callable[[jax.Array, Any], Any]
    message: Callable[[Any, Any, Any], Any]    # (j, src_state[E], edge_data) -> payload[E]
    apply: Callable[[Any, Any, Any, Any], Tuple[Any, jax.Array]]
    combine: str = "sum"
    name: str = "pregel-task"

    def program(self) -> Program:
        monoid = get_monoid(self.combine)
        # The monoid's own idempotence travels into the logical layer;
        # every Pregel inbox is additionally recomputed from scratch each
        # superstep (collect@J derives solely from send@J), which licenses
        # the semi-naive rewrite even for non-idempotent combines.
        return pregel_program(
            udfs={"init_vertex": self.init_vertex, "update": self.apply},
            aggregates={"combine": monoid.as_aggregate(recomputable=True)},
        )


@dataclass
class PregelExecutable:
    prog: VertexProgram
    program: Program
    logical: algebra.LogicalPlan
    plan: PregelPhysicalPlan
    superstep: Callable[[Any, Any], Any]   # ((state, active), j) -> (state, active)
    graph: Graph
    mesh: Optional[Mesh]
    semi_naive: bool = False
    # Sparse (delta-frontier) execution runs on every edge layout: the
    # single-shard slab, and sharded meshes via per-shard compaction under
    # ``shard_map``.  The factory builds the jitted frontier-compacted
    # superstep for a given static capacity (see
    # :func:`repro.core.executor.build_pregel_steps`).
    supports_sparse: bool = True
    sparse_step_factory: Optional[Callable[[int], Callable]] = field(
        default=None, repr=False
    )
    # Sharded meshes: ``active -> int32[n_shards]`` shard-local active-edge
    # counts (one tiny shard_map reduction, read on host).
    shard_count_fn: Optional[Callable] = field(default=None, repr=False)
    # Per-shard edge-slab size (== n_edges on the single-shard layout): a
    # compaction capacity at or above this cannot win, so the adaptive
    # driver falls back to the lossless frontier-masked dense path.
    local_edge_cap: int = 0
    _sparse_steps: Dict[int, Callable] = field(default_factory=dict, repr=False)
    _edge_count_fn: Optional[Callable] = field(default=None, repr=False)
    _jit_superstep: Optional[Callable] = field(default=None, repr=False)
    _halt_step: Optional[Callable] = field(default=None, repr=False)
    # Elastic fault tolerance: the failure injector threaded from compile
    # (honored at the host step boundary), one note per remesh in this
    # executable's lineage, and the compile kwargs :meth:`remesh` needs to
    # re-derive the physical plan for a surviving topology.
    injector: Optional[Any] = None
    remesh_events: Tuple[str, ...] = ()
    _compile_kwargs: Dict[str, Any] = field(default_factory=dict, repr=False)

    @property
    def sparse_cap_floor(self) -> int:
        return self.plan.sparse_cap_floor

    @property
    def jitted_superstep(self) -> Callable:
        """The dense superstep under :class:`~repro.core.fixpoint.
        jit_hoisted` (cached, graph arrays as arguments) — host-driver and
        adaptive runs must not fall back to op-by-op eager dispatch."""

        if self._jit_superstep is None:
            self._jit_superstep = jit_hoisted(self.superstep)
        return self._jit_superstep

    def _place_carry(self, carry: Any) -> Any:
        """Commit a restored host-side carry onto this executable's device
        set.  Checkpoints are stored unsharded; ``restore`` commits the
        arrays to the ``like`` tree's (single) device, and a single-device
        committed array cannot feed the ``shard_map`` superstep spanning
        the mesh.  Replicated placement is always valid — jit reshards to
        the superstep's specs on entry — and is what lets an 8-shard run's
        checkpoint resume on a 4-shard mesh after :meth:`remesh`."""

        if self.mesh is None:
            return carry
        sharding = NamedSharding(self.mesh, PartitionSpec())
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sharding), carry
        )

    def init(self) -> Tuple[Any, jax.Array]:
        ids = jnp.arange(self.graph.n_vertices, dtype=jnp.int32)
        state = self.prog.init_vertex(ids, self.graph.vertex_data)
        active = jnp.ones((self.graph.n_vertices,), dtype=jnp.bool_)
        return state, active

    @staticmethod
    def converged(prev, new) -> jax.Array:
        _, active = new
        return jnp.logical_not(jnp.any(active))

    # -- semi-naive (delta-frontier) execution ------------------------------

    def active_edge_count(self, active: jax.Array) -> int:
        """|Δ frontier| in edges: how many edges originate at active
        vertices this superstep (one tiny jitted reduction, read on host)."""

        if self._edge_count_fn is None:
            src = self.graph.src
            self._edge_count_fn = jit_hoisted(jax.named_scope("compact")(
                lambda a: jnp.sum(jnp.take(a, src).astype(jnp.int32))
            ))
        return int(self._edge_count_fn(active))

    def shard_edge_counts(self, active: jax.Array) -> np.ndarray:
        """Shard-local active-edge counts, int array of length n_shards.

        On sharded meshes this is one collective read per superstep: every
        shard reduces its own edge slab and the host driver aggregates the
        counts into a single dense<->sparse decision (sum -> density for the
        mode, max -> per-shard compaction capacity), so all shards execute
        the same superstep variant in SPMD lockstep."""

        if self.shard_count_fn is None:
            return np.asarray([self.active_edge_count(active)])
        return np.asarray(self.shard_count_fn(active))

    def sparse_superstep(self, cap: int) -> Callable:
        """Jitted frontier-compacted superstep for a given static capacity
        (cached per capacity — the adaptive driver walks a power-of-two
        ladder, so only O(log E) variants ever compile).  The variant comes
        from the executor's ``sparse_step_factory`` (per-shard compaction
        under ``shard_map`` on meshes, the plain compacted slab otherwise).
        """

        fn = self._sparse_steps.get(cap)
        if fn is None:
            if self.sparse_step_factory is None:
                raise ValueError(
                    "PregelExecutable has no sparse_step_factory — build "
                    "it through compile_pregel (executor.build_pregel_steps"
                    " supplies the factory on every layout)"
                )
            fn = self.sparse_step_factory(cap)
            self._sparse_steps[cap] = fn
        return fn

    def sparse_cap_for(self, count: int) -> int:
        """Compaction capacity for a measured (max shard-local) active-edge
        count — delegates to the plan, the planner-derived single source of
        the cap ladder, so benchmarks time exactly what the adaptive driver
        runs."""

        return self.plan.sparse_cap_for(count)

    def halt_superstep(self) -> Callable:
        """Algebraically-simplified superstep for an all-empty edge
        frontier: no edge can carry a message, so ``got`` is False
        everywhere and the full superstep reduces to keeping the state and
        clearing the active flags — O(N) bool work instead of a
        cap-floor-sized compact/exchange no-op.  Running it (rather than
        skipping the iteration) keeps ONE termination mechanism — the
        driver's ``converged`` test — and leaves exactly the state/active
        pair the dense path would produce."""

        if self._halt_step is None:
            self._halt_step = jax.jit(
                lambda carry, j: (carry[0], jnp.zeros_like(carry[1]))
            )
        return self._halt_step

    def adaptive_select_step(
        self, carry, j: int
    ) -> Tuple[Callable, str]:
        """Per-superstep dense<->sparse choice (the Fig. 9 connector choice
        recomputed online): measure the frontier density, consult the plan's
        cost-model threshold, and pick the executing superstep.  Dense early
        (everything active), sparse in the long convergence tail.

        On sharded meshes the shard-local counts are aggregated into ONE
        decision (sum -> density, max -> capacity) so every shard runs the
        same compiled variant — SPMD lockstep.  An all-empty frontier means
        no rule can fire: the selector swaps in :meth:`halt_superstep`
        (clear the active flags, O(N)) instead of a cap-floor-sized no-op
        compact/exchange superstep, and the fixpoint converges this
        iteration.  A frontier too large for the per-shard slab (capacity
        overflow) falls back to the lossless frontier-masked dense path —
        compaction never silently drops messages."""

        _, active = carry
        counts = self.shard_edge_counts(active)
        total = int(counts.sum())
        if total == 0:
            halt = self.halt_superstep()
            return (lambda s, jj: halt(s, jnp.int32(jj))), "halt(empty-frontier)"
        density = total / max(self.graph.n_edges, 1)
        if (
            self.supports_sparse
            and self.plan.mode_for_density(density) == "sparse"
        ):
            cap = self.sparse_cap_for(int(counts.max()))
            if cap < self.local_edge_cap:
                fn = self.sparse_superstep(cap)
                return (lambda s, jj: fn(s, jnp.int32(jj))), f"sparse@{cap}"
        dense = self.jitted_superstep
        return (lambda s, jj: dense(s, jnp.int32(jj))), "dense"

    # -- fixpoint entry points ---------------------------------------------

    def run(
        self,
        max_iters: int,
        on_device: Optional[bool] = None,
        adaptive: Optional[bool] = None,
        *,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        injector: Optional[Any] = None,
        max_restarts: int = 3,
        keep_checkpoints: int = 3,
    ) -> FixpointResult:
        """Run to the Appendix-B.2 fixpoint.

        Semi-naive plans default to the host driver with per-superstep
        adaptive dense/sparse selection (shape-changing compaction cannot
        live inside one ``lax.while_loop``); dense plans default on-device.
        An explicit ``on_device=True`` is honored — it disables adaptive
        selection (the two are mutually exclusive; requesting both raises).

        Fault tolerance (host driver only): ``checkpoint_dir`` checkpoints
        the ``(state, active)`` carry host-side every ``checkpoint_every``
        supersteps (default 8) through a
        :class:`~repro.checkpoint.CheckpointStore`; a crash restores and
        replays, and ``resume=True`` continues a run from disk — including
        onto a *different* mesh after :meth:`remesh`.  ``injector``
        overrides the compile-time :class:`~repro.ft.elastic.
        FailureInjector` at the step boundary.
        """

        if on_device and adaptive:
            raise ValueError(
                "on_device=True and adaptive=True are incompatible: "
                "adaptive dense/sparse selection needs the host driver"
            )
        injector = self.injector if injector is None else injector
        ft = checkpoint_dir is not None or injector is not None
        if on_device and ft:
            raise ValueError(
                "fault tolerance (checkpoint_dir/injector) needs the host "
                "driver: pass on_device=False"
            )
        if resume and checkpoint_dir is None:
            raise ValueError("resume=True needs checkpoint_dir=")
        if adaptive is None:
            adaptive = (
                self.semi_naive and self.supports_sparse and not on_device
            )
        if on_device is None:
            on_device = not adaptive and not ft
        init = self.init()
        if on_device and not adaptive:
            return device_fixpoint(
                self.superstep, self.converged, init, max_iters
            )
        store, start_iter = None, 0
        save_hook = restore_hook = None
        if checkpoint_dir is not None:
            from repro.checkpoint import CheckpointStore, latest_step

            store = CheckpointStore(checkpoint_dir, keep=keep_checkpoints)
            if checkpoint_every <= 0:
                checkpoint_every = 8

            def save_hook(carry, j):
                store.save(j, carry, extra={"iteration": j})

            def restore_hook():
                carry, j, _ = store.restore(like=self.init())
                return self._place_carry(carry), int(j)

            if resume and latest_step(checkpoint_dir) is not None:
                init, start_iter, _ = store.restore(like=self.init())
                init = self._place_carry(init)
                start_iter = int(start_iter)
        driver = HostFixpointDriver(
            step=lambda s, j: self.jitted_superstep(s, jnp.int32(j)),
            converged=self.converged,
            config=DriverConfig(
                max_iters=max_iters,
                checkpoint_every=checkpoint_every if store else 0,
                max_restarts=max_restarts,
            ),
            save=save_hook,
            restore=restore_hook,
            select_step=self.adaptive_select_step if adaptive else None,
            injector=injector,
        )
        if store is not None and start_iter == 0:
            # Entry restore point: a crash before the first periodic save
            # must still have somewhere to rewind to.
            save_hook(init, 0)
        try:
            res = driver.run(init, start_iter=start_iter)
        except BaseException:
            # drain the async writer before the failure propagates, so it
            # cannot race a successor run over the same checkpoint directory
            if store is not None:
                store.quiesce()
            raise
        if store is not None:
            store.wait()  # surface any pending async-save failure
        if self.remesh_events:
            res = replace(res, remesh_events=self.remesh_events)
        return res

    def driver(
        self,
        config: DriverConfig,
        adaptive: Optional[bool] = None,
        **hooks,
    ) -> HostFixpointDriver:
        if adaptive is None:
            adaptive = self.semi_naive and self.supports_sparse
        hooks.setdefault("injector", self.injector)
        return HostFixpointDriver(
            step=lambda s, j: self.jitted_superstep(s, jnp.int32(j)),
            converged=self.converged,
            config=config,
            select_step=self.adaptive_select_step if adaptive else None,
            **hooks,
        )

    def remesh(self, mesh: Optional[Mesh]) -> "PregelExecutable":
        """Recompile this vertex program onto a new (typically shrunken)
        mesh after device loss: ``plan_pregel`` re-derives the physical
        plan for the surviving topology, the edge slabs are re-partitioned,
        and the remesh is recorded in ``plan.notes`` and carried into
        ``FixpointResult.remesh_events``.  Host-side checkpoints written by
        the old executable restore directly into the new one (the carry is
        stored unsharded)."""

        old_n = 1 if self.mesh is None else int(self.mesh.devices.size)
        new = compile_pregel(
            self.prog, self.graph, mesh=mesh, semi_naive=self.semi_naive,
            **self._compile_kwargs,
        )
        if mesh is None:
            shape, new_n = "1 device", 1
        else:
            shape = "x".join(
                f"{n}={s}"
                for n, s in zip(mesh.axis_names, mesh.devices.shape)
            )
            new_n = int(mesh.devices.size)
        note = f"remesh({old_n}->{new_n}: {shape})"
        new.plan = replace(new.plan, notes=new.plan.notes + (note,))
        new.remesh_events = self.remesh_events + (note,)
        new.injector = self.injector
        return new


def compile_pregel(
    prog: VertexProgram,
    graph: Graph,
    *,
    mesh: Optional[Mesh] = None,
    mesh_spec: Optional[MeshSpec] = None,
    hw: HardwareSpec = TPU_V5E,
    force_connector: Optional[str] = None,
    payload_bytes: int = 4,
    semi_naive: bool = False,
    injector: Optional[Any] = None,
) -> PregelExecutable:
    """Compile a vertex program through the declarative stack (Fig. 1).

    ``semi_naive=True`` enables delta-frontier evaluation: the logical plan's
    eligible recursive reads become ``Delta`` scans (semi-naive rewrite), the
    physical plan gains a frontier-density threshold from the cost model, and
    the executable carries frontier-compacted sparse supersteps that the
    adaptive driver swaps in when the measured density drops below it.

    ``graph.edge_data`` (weighted graphs) runs on every layout: sharded
    meshes partition each leaf into the per-shard edge slabs, and the
    planner's cost terms account for the per-edge attribute bytes
    (``PregelStats.edge_attr_bytes``, recorded in ``plan.notes``).

    ``prog.combine`` names any registered :class:`~repro.core.monoid.
    CombineMonoid`.  The message payload's shape is probed (shape-only
    ``jax.eval_shape`` of the init/message UDFs, no FLOPs) so structured
    monoids validate their width before anything compiles and the planner
    prices the true per-message bytes (``PregelStats.msg_bytes`` /
    ``combine`` — the payload-width cost terms); ``payload_bytes`` is the
    fallback when the probe cannot run.
    """

    monoid = get_monoid(prog.combine)

    # Per-edge attribute payload width (weighted graphs): bytes of edge_data
    # gathered per edge, fed to the planner's weighted cost terms.
    edge_attr_bytes = 0
    if graph.edge_data is not None:
        for leaf in jax.tree_util.tree_leaves(graph.edge_data):
            shape = getattr(leaf, "shape", None)
            if shape is None or len(shape) < 1 or shape[0] != graph.n_edges:
                raise ValueError(
                    "every edge_data leaf needs leading dim n_edges "
                    f"({graph.n_edges}); got shape {shape}"
                )
            edge_attr_bytes += np.dtype(leaf.dtype).itemsize * int(
                np.prod(shape[1:], dtype=np.int64)
            )

    # Message-payload probe: abstract evaluation of init_vertex + message
    # gives the payload's shape/dtype without running either UDF.  Width
    # violations (e.g. an argmin payload without its key column) surface
    # here, at compile, rather than as a shape error mid-superstep.
    msg_bytes = payload_bytes
    try:
        ids_s = jax.ShapeDtypeStruct((graph.n_vertices,), jnp.int32)
        state_s = jax.eval_shape(prog.init_vertex, ids_s, graph.vertex_data)
        src_state_s = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                (graph.n_edges,) + s.shape[1:], s.dtype
            ),
            state_s,
        )
        edata_s = (
            None if graph.edge_data is None else jax.tree_util.tree_map(
                lambda e: jax.ShapeDtypeStruct(
                    (graph.n_edges,) + e.shape[1:], e.dtype
                ),
                graph.edge_data,
            )
        )
        payload_s = jax.eval_shape(
            prog.message, jnp.int32(0), src_state_s, edata_s
        )
    except Exception:
        payload_s = None  # shape probe is best-effort for exotic UDFs
    if payload_s is not None:
        monoid.validate_payload(payload_s.shape, payload_s.dtype)
        msg_bytes = np.dtype(payload_s.dtype).itemsize * max(
            int(np.prod(payload_s.shape[1:], dtype=np.int64)), 1
        )

    # (1)-(3): Datalog -> XY schedule -> Figure-3 logical plan.
    program = prog.program()
    schedule = stratify.iteration_schedule(program)
    assert tuple(r.label for r in schedule.init_rules) == ("L1", "L2")
    logical = algebra.translate(program)
    sn_notes: Tuple[str, ...] = ()
    if semi_naive:
        logical, sn_notes = algebra.semi_naive_rewrite(logical, program)

    # (4): physical plan from graph statistics.
    if mesh_spec is None:
        if mesh is not None:
            mesh_spec = MeshSpec(
                tuple((n, s) for n, s in zip(mesh.axis_names, mesh.devices.shape))
            )
        else:
            mesh_spec = MeshSpec((("data", 1),))
    stats = PregelStats(
        n_vertices=graph.n_vertices,
        n_edges=graph.n_edges,
        vertex_bytes=payload_bytes,
        msg_bytes=msg_bytes,
        edge_attr_bytes=edge_attr_bytes,
        combine=prog.combine,
    )
    plan = plan_pregel(
        stats, mesh_spec, hw, force_connector=force_connector,
        semi_naive=semi_naive, extra_notes=sn_notes,
    )

    # (5): the unified executor materializes the planned superstep pipeline
    # (dense shard_map step + frontier-compacted sparse variants).
    bundle = build_pregel_steps(prog, graph, plan, mesh, injector=injector)

    return PregelExecutable(
        prog=prog,
        program=program,
        logical=logical,
        plan=plan,
        superstep=bundle.superstep,
        graph=graph,
        mesh=mesh,
        semi_naive=semi_naive,
        supports_sparse=True,
        sparse_step_factory=bundle.sparse_step_factory,
        shard_count_fn=bundle.shard_count_fn,
        local_edge_cap=bundle.local_edge_cap,
        injector=bundle.injector,
        _compile_kwargs={
            "hw": hw, "force_connector": force_connector,
            "payload_bytes": payload_bytes,
        },
    )
