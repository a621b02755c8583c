"""Fixpoint drivers for XY-stratified programs (paper §3.3, Appendix B.2).

Two drivers implement the iterate-to-fixpoint semantics of an XY-stratified
program (initialization stratum once, then per-iteration rule firings until
no new facts are derived):

* :func:`device_fixpoint` — the whole loop lives on device as a
  ``lax.while_loop`` whose carried state is the recursive-predicate frontier
  (model/vertex/send arrays).  Loop-invariant EDB relations are closed
  over and passed to the compiled loop as device-resident arguments
  (:class:`jit_hoisted`), read by every iteration without moving — the
  paper's HaLoop-style "loop-invariant caching", which is what let Hyracks
  beat Hadoop by an order of magnitude in §5.2.

* :class:`HostFixpointDriver` — a production driver that runs one jitted
  iteration per host step so it can interleave checkpointing, failure
  detection/restart, elastic re-planning, and straggler mitigation between
  iterations.  This is the paper's "iteration driver" (Fig. 1) grown the
  fault-tolerance features demanded at pod scale.

Termination mirrors Appendix B.2: either the temporal argument hits its
finite bound (``max_iters``) or the update UDF derives no new facts
(``converged(state)`` — e.g. G3's ``M != NewM`` is empty, L8's send set is
empty).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "FixpointResult",
    "device_fixpoint",
    "HostFixpointDriver",
    "DriverConfig",
    "jit_hoisted",
]

logger = logging.getLogger(__name__)


class jit_hoisted:
    """``jax.jit`` that passes the arrays ``fn`` closes over as arguments.

    A plain ``jax.jit`` embeds every array a function closes over in the
    compiled program as a constant: a 67M-edge list or a 4 GiB record set
    would travel inside the HLO, and XLA constant-folds whole sorts of it
    at compile time.  Steps here close over their loop-invariant EDB by
    design, so each input signature is traced once with
    ``jax.make_jaxpr``, the closed-over arrays (the jaxpr's consts) are
    lifted out, and the jitted program takes them as ordinary device
    arguments — placed once, resident across calls.  ``lower`` mirrors
    ``jax.jit(fn).lower`` for inspecting the compiled step, and
    ``compiled`` returns the executables the calls so far ran."""

    def __init__(self, fn: Callable):
        self.fn = fn
        self._traced: dict = {}

    def _entry(self, args):
        flat, tree = jax.tree_util.tree_flatten(args)
        key = (tree, tuple(jax.typeof(a) for a in flat))
        entry = self._traced.get(key)
        if entry is None:
            closed, out_shape = jax.make_jaxpr(
                self.fn, return_shape=True
            )(*args)
            jaxpr = closed.jaxpr
            run = jax.jit(
                lambda consts, *xs: jax.core.eval_jaxpr(jaxpr, consts, *xs)
            )
            consts = [jax.device_put(c) if isinstance(c, np.ndarray) else c
                      for c in closed.consts]
            specs = [jax.ShapeDtypeStruct(
                t.shape, t.dtype, weak_type=t.weak_type,
                sharding=a.sharding if isinstance(a, jax.Array)
                and not isinstance(a, jax.core.Tracer) and a.committed
                else None,
            ) for a, t in zip(flat, key[1])]
            entry = (run, consts, jax.tree_util.tree_structure(out_shape),
                     specs)
            self._traced[key] = entry
        return entry, flat

    def __call__(self, *args):
        (run, consts, out_tree, _), flat = self._entry(args)
        return jax.tree_util.tree_unflatten(out_tree, run(consts, *flat))

    def lower(self, *args):
        (run, consts, _, _), flat = self._entry(args)
        return run.lower(consts, *flat)

    def compiled(self) -> list:
        """One ``jax.stages.Compiled`` per input signature traced so far:
        the executables the calls ran (JAX's compile caches return them,
        so nothing is compiled again)."""

        return [run.lower(consts, *specs).compile()
                for run, consts, _, specs in self._traced.values()]


@dataclass
class FixpointResult:
    state: Any
    iterations: int
    converged: bool
    seconds: float = 0.0
    restarts: int = 0
    # Per-iteration execution mode labels when an adaptive step selector ran
    # ("dense" / "sparse@<cap>"); empty otherwise.
    modes: Tuple[str, ...] = ()
    # Multi-stratum programs (the generic executor): iterations spent in each
    # sequential fixpoint phase, in phase order; empty for single-loop runs.
    phase_iterations: Tuple[int, ...] = ()
    # Fault-tolerance accounting: slow-iteration detections, and one note per
    # elastic remesh the executable went through (e.g. "remesh(8->4: ...)").
    straggler_events: int = 0
    remesh_events: Tuple[str, ...] = ()
    # True when a row-table run overflowed its static capacity and the
    # executor transparently re-ran the program on dense-grid storage.
    storage_fallback: bool = False


def device_fixpoint(
    body: Callable[[Any, jax.Array], Any],
    converged: Callable[[Any, Any], jax.Array],
    init_state: Any,
    max_iters: int,
    donate: bool = True,
) -> FixpointResult:
    """Run the per-iteration stratum to fixpoint entirely on device.

    ``body(state, j) -> state`` fires the iteration's rules (X-rules then
    Y-rules, already scheduled by the stratifier); ``converged(prev, new)``
    implements the no-new-facts test.  The whole loop compiles to a single
    XLA ``while`` — zero host round-trips per iteration.
    """

    def cond(carry):
        state, j, done = carry
        return jnp.logical_and(j < max_iters, jnp.logical_not(done))

    def step(carry):
        state, j, _ = carry
        new_state = body(state, j)
        done = converged(state, new_state)
        return new_state, j + 1, done

    t0 = time.perf_counter()
    fn = jit_hoisted(
        lambda s: lax.while_loop(
            cond, step, (s, jnp.int32(0), jnp.bool_(False)))
    )
    state, iters, done = fn(init_state)
    state = jax.block_until_ready(state)
    return FixpointResult(
        state=state,
        iterations=int(iters),
        converged=bool(done),
        seconds=time.perf_counter() - t0,
    )


# ---------------------------------------------------------------------------
# Host driver: checkpointing, fault tolerance, elasticity, stragglers
# ---------------------------------------------------------------------------


# XLA status codes of errors that replaying the iteration would raise again.
_DETERMINISTIC_STATUS = (
    "INVALID_ARGUMENT", "UNIMPLEMENTED", "RESOURCE_EXHAUSTED",
    "FAILED_PRECONDITION",
)


def _replayable(exc: Exception) -> bool:
    """Whether restore-and-replay can get past ``exc``: a device or host
    failure (a ``RuntimeError``, which injected crashes are too).  Tracing,
    lowering and compile errors, and running out of device memory, recur
    on every replay, so they surface at once instead of being retried."""

    if not isinstance(exc, RuntimeError) or isinstance(
            exc, (NotImplementedError, RecursionError)):
        return False
    if isinstance(exc, jax.errors.JaxRuntimeError):
        msg = str(exc)
        return not (msg.startswith(_DETERMINISTIC_STATUS)
                    or "compil" in msg.lower())
    return True


@dataclass
class DriverConfig:
    max_iters: int = 1000
    checkpoint_every: int = 0            # 0 = disabled
    checkpoint_dir: Optional[str] = None
    keep_checkpoints: int = 3
    max_restarts: int = 3
    # Straggler mitigation: if an iteration exceeds ``straggler_factor`` x the
    # trailing-mean iteration time, log + count it (on real pods: re-issue the
    # slow shard's collective participant / drop to backup reducer).
    straggler_factor: float = 3.0
    log_every: int = 10


class HostFixpointDriver:
    """Fault-tolerant host-side fixpoint loop.

    The driver owns the loop skeleton; the *plan* supplies three callables:

    * ``step(state, j) -> state`` — one jitted iteration (the physical plan).
    * ``converged(prev, new) -> bool-array`` — the no-new-facts test.
    * optional ``save(state, j)`` / ``restore() -> (state, j)`` hooks, wired
      to :mod:`repro.checkpoint` by the launchers.

    Failure handling: a device or host failure inside ``step`` triggers
    restore from the last checkpoint and replay (compile and programming
    errors are deterministic and re-raise at once — see
    :func:`_replayable`; at-least-once, idempotent because iterations
    are pure functions of state — the Datalog semantics guarantee exactly the
    paper's re-execution story: "the logic for incremental evaluation and
    re-execution in the face of failures" lives below the user program).
    """

    def __init__(
        self,
        step: Callable[[Any, int], Any],
        converged: Callable[[Any, Any], Any],
        config: Optional[DriverConfig] = None,
        save: Optional[Callable[[Any, int], None]] = None,
        restore: Optional[Callable[[], Tuple[Any, int]]] = None,
        on_iteration: Optional[Callable[[int, float], None]] = None,
        select_step: Optional[
            Callable[[Any, int], Tuple[Callable[[Any, int], Any], str]]
        ] = None,
        injector: Optional[Any] = None,
        on_straggler: Optional[Callable[[int, float], None]] = None,
    ) -> None:
        self.step = step
        self.converged = converged
        # A fresh config per driver: a shared default instance would leak
        # config mutations across drivers.
        self.config = DriverConfig() if config is None else config
        self.save = save
        self.restore = restore
        self.on_iteration = on_iteration
        # Failure injection at the step boundary (chaos tests / benchmarks):
        # an ``ft.elastic.FailureInjector`` whose ``maybe_fail(j)`` raises
        # (crash — handled by the restore path below) or sleeps (straggle —
        # inflates this iteration's wall time so detection fires).
        self.injector = injector
        # Straggler-mitigation hook: called as ``on_straggler(j, dt)`` when
        # an iteration exceeds the straggler threshold.  IMRU uses it to fall
        # back to the k-ary aggregation tree (fewer synchronous neighbors).
        self.on_straggler = on_straggler
        # Adaptive execution (semi-naive Pregel): ``select_step(state, j)``
        # inspects the carried state (e.g. measures the active frontier
        # density) and returns ``(step_fn, mode_label)`` for this iteration —
        # the plan's dense<->sparse choice recomputed online.  Labels are
        # recorded in ``mode_history`` for tests and EXPERIMENTS.md.
        self.select_step = select_step
        self.mode_history: list[str] = []
        self.iter_times: list[float] = []
        self.straggler_events = 0
        self.restarts = 0
        # Straggler window start: iterations recorded before the most recent
        # restart are excluded from the trailing mean (their times belong to
        # the failed attempt and would pollute the baseline).
        self._window_start = 0
        # Single-shot fault injection (testing) — instance state, so one
        # driver's injected failure can never leak into another.
        self.fail_at: Optional[int] = None
        self._failed_once = False

    def run(self, init_state: Any, start_iter: int = 0) -> FixpointResult:
        state, j = init_state, start_iter
        cfg = self.config
        t_start = time.perf_counter()
        done = False
        while j < cfg.max_iters and not done:
            t0 = time.perf_counter()
            try:
                if self.fail_at is not None and j == self.fail_at \
                        and not self._failed_once:
                    self._failed_once = True
                    raise RuntimeError(f"injected failure at iteration {j}")
                if self.injector is not None:
                    self.injector.maybe_fail(j)
                step_fn = self.step
                if self.select_step is not None:
                    step_fn, mode = self.select_step(state, j)
                    self.mode_history.append(mode)
                new_state = step_fn(state, j)
                new_state = jax.block_until_ready(new_state)
            except Exception as exc:  # noqa: BLE001 — FT boundary
                if not _replayable(exc):
                    raise
                self.restarts += 1
                if self.restarts > cfg.max_restarts or self.restore is None:
                    raise
                logger.warning(
                    "iteration %d failed (%s); restoring from checkpoint "
                    "(restart %d/%d)", j, exc, self.restarts, cfg.max_restarts
                )
                state, j = self.restore()
                # Iteration times recorded before the failure belong to the
                # aborted attempt; restart the straggler window so the
                # trailing mean reflects only post-restore iterations.
                self._window_start = len(self.iter_times)
                # Drop mode labels recorded for the failed attempt and for
                # iterations about to be replayed, keeping mode_history[i]
                # aligned with iteration start_iter + i.
                del self.mode_history[max(j - start_iter, 0):]
                continue

            dt = time.perf_counter() - t0
            self.iter_times.append(dt)
            window = self.iter_times[self._window_start:]
            if len(window) > 3:
                trailing = sum(window[-11:-1]) / len(window[-11:-1])
                if dt > cfg.straggler_factor * trailing:
                    self.straggler_events += 1
                    logger.warning(
                        "straggler: iteration %d took %.3fs (%.1fx trailing "
                        "mean %.3fs)", j, dt, dt / trailing, trailing,
                    )
                    if self.on_straggler is not None:
                        self.on_straggler(j, dt)

            done = bool(self.converged(state, new_state))
            state = new_state
            j += 1
            if self.on_iteration is not None:
                self.on_iteration(j, dt)
            if cfg.checkpoint_every and self.save is not None \
                    and j % cfg.checkpoint_every == 0:
                self.save(state, j)
            if cfg.log_every and j % cfg.log_every == 0:
                logger.info("iteration %d done in %.3fs", j, dt)

        if self.save is not None and cfg.checkpoint_every:
            self.save(state, j)
        return FixpointResult(
            state=state,
            iterations=j - start_iter,
            converged=done,
            seconds=time.perf_counter() - t_start,
            restarts=self.restarts,
            modes=tuple(self.mode_history),
            straggler_events=self.straggler_events,
        )
