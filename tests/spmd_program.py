"""Multi-device SPMD validation program, run as a subprocess by
test_spmd.py (the XLA device-count flag must be set before jax imports, and
the main test process must keep seeing 1 device)."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import sys

import numpy as np
import jax
import jax.numpy as jnp


def main() -> None:
    results = {}
    auto = jax.sharding.AxisType.Auto
    mesh2 = jax.make_mesh((4, 2), ("data", "model"), axis_types=(auto,) * 2)
    mesh3 = jax.make_mesh(
        (2, 2, 2), ("pod", "data", "model"), axis_types=(auto,) * 3
    )

    # --- IMRU: every reduce schedule reaches the same fixpoint -------------
    from repro.core.imru import IMRUTask, compile_imru

    rng = np.random.default_rng(0)
    n, d = 512, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d,)).astype(np.float32)
    y = X @ w_true
    lr = 0.01 / n
    finals = {}
    for sched in ("flat", "hierarchical", "kary_tree", "scatter"):
        task = IMRUTask(
            init_model=lambda: jnp.zeros((d,), jnp.float32),
            map=lambda rec, m: ((rec["x"] @ m - rec["y"]) @ rec["x"]),
            update=lambda j, m, g: m - lr * g,
            tol=1e-7,
        )
        ex = compile_imru(
            task, {"x": jnp.asarray(X), "y": jnp.asarray(y)},
            mesh=mesh3, force_reduce=sched,
        )
        res = ex.run(max_iters=1500)
        finals[sched] = np.asarray(res.state)
    base = finals["flat"]
    results["imru_schedules_agree"] = bool(all(
        np.allclose(base, v, atol=1e-6) for v in finals.values()
    ))
    results["imru_err_vs_true"] = float(np.max(np.abs(base - w_true)))

    # --- int8 error-feedback codec converges too ---------------------------
    from repro.optim.compression import ef_int8_allreduce, init_ef_state
    from jax.experimental.shard_map import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    Xs = jax.device_put(
        jnp.asarray(X), NamedSharding(mesh2, P(("data",), None)))
    ys = jax.device_put(
        jnp.asarray(y), NamedSharding(mesh2, P(("data",))))

    def step(w, resid):
        def shard_fn(xx, yy, w, r):
            g = (xx @ w - yy) @ xx
            (g_sum,), st = ef_int8_allreduce(
                (g,), init_ef_state((g,))._replace(residuals=(r,)),
                axes=("data",),
            )
            return w - lr * g_sum, st.residuals[0]

        return shard_map(
            shard_fn, mesh=mesh2,
            in_specs=(P(("data",), None), P(("data",)), P(), P()),
            out_specs=(P(), P()),
            check_rep=False,
        )(Xs, ys, w, resid)

    # NOTE: block every step — concurrently in-flight executions that each
    # contain collectives can interleave their device rendezvous on the CPU
    # backend and deadlock (XLA kills the process after 40 s).
    w = jnp.zeros(d, jnp.float32)
    resid = jnp.zeros(d, jnp.float32)
    stepj = jax.jit(step)
    for _ in range(500):
        w, resid = stepj(w, resid)
        jax.block_until_ready(w)
    results["int8_ef_err_vs_true"] = float(np.max(np.abs(
        np.asarray(w) - w_true)))

    # --- Pregel: sharded connectors match the numpy oracle -----------------
    from repro.core.pregel import Graph, VertexProgram, compile_pregel

    N = 64
    rng = np.random.default_rng(1)
    src, dst = [], []
    for v in range(N):
        for _ in range(rng.integers(1, 5)):
            src.append(v)
            dst.append(int(rng.integers(0, N)))
    for v in range(N):
        src.append(int(rng.integers(0, N)))
        dst.append(v)
    src = np.array(src, np.int32)
    dst = np.array(dst, np.int32)
    outdeg = np.bincount(src, minlength=N).astype(np.float32)
    P_ = np.zeros((N, N))
    for s_, d_ in zip(src, dst):
        P_[d_, s_] += 1.0 / outdeg[s_]
    r = np.full(N, 1.0 / N)
    for _ in range(30):
        r = 0.15 / N + 0.85 * P_ @ r

    errs = {}
    for conn in ("dense_psum", "merging", "hash_sort"):
        g = Graph(N, jnp.asarray(src), jnp.asarray(dst),
                  jnp.asarray(outdeg))
        prog = VertexProgram(
            init_vertex=lambda ids, vd: jnp.stack(
                [jnp.full((N,), 1.0 / N), jnp.asarray(outdeg)], axis=1),
            message=lambda j, s, ed: s[:, 0] / jnp.maximum(s[:, 1], 1.0),
            apply=lambda j, s, inbox, got: (
                jnp.stack([0.15 / N + 0.85 * inbox, s[:, 1]], axis=1),
                jnp.ones(s.shape[0], jnp.bool_),
            ),
            combine="sum",
        )
        ex = compile_pregel(prog, g, mesh=mesh2, force_connector=conn)
        res = ex.run(max_iters=30)
        errs[conn] = float(np.max(np.abs(
            np.asarray(res.state[0][:, 0]) - r)))
    results["pregel_errs"] = errs

    # --- LM train step under a real (tiny) mesh ----------------------------
    import dataclasses

    from repro.core.lm_planner import plan_lm
    from repro.core.hardware import MeshSpec
    from repro.launch.train import build_train_step, param_shardings
    from repro.models import lm as lm_mod
    from repro.models.registry import get_config, reduced_config
    from repro.optim import adamw

    cfg = reduced_config(get_config("minitron_8b"))
    spec = MeshSpec((("data", 4), ("model", 2)))
    plan = plan_lm(cfg, "train_4k", spec)
    plan = dataclasses.replace(plan, cfg=cfg, microbatches=2)
    opt = adamw(lr=1e-3)
    step, state_sh, bsh = build_train_step(plan, mesh2, optimizer=opt)
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(0))
    params = jax.device_put(params, state_sh["params"])
    opt_state = jax.device_put(opt.init(params), state_sh["opt"])
    state = {"params": params, "opt": opt_state,
             "step": jax.device_put(jnp.int32(0), state_sh["step"])}
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (8, 32)), jnp.int32)
    batch = {"tokens": jax.device_put(toks, bsh({"tokens": toks})["tokens"])}
    losses = []
    for i in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    results["lm_sharded_losses"] = losses
    results["lm_sharded_decreasing"] = bool(losses[-1] < losses[0])

    print("RESULTS_JSON:" + json.dumps(results))


if __name__ == "__main__":
    main()
