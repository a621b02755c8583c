import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell on the production mesh and record the roofline inputs.

MUST be run as its own process (the two lines above lock jax to 512
placeholder host devices before any other import — never set that flag
globally).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch minitron_8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all          # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

``--all`` spawns one subprocess per cell (compile state isolation + crash
containment) and skips cells whose JSON artifact already exists (pass
``--force`` to redo).  Artifacts land in artifacts/dryrun/<cell>.json and
are consumed by benchmarks/roofline.py and EXPERIMENTS.md.
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

ARTIFACT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "artifacts", "dryrun",
)


def _cell_name(arch: str, shape: str, mesh: str, variant: str = "") -> str:
    v = f"__{variant}" if variant else ""
    return f"{arch}__{shape}__{mesh}{v}"


def run_cell(arch: str, shape: str, mesh_kind: str,
             variant: str = "", overrides: Optional[Dict] = None,
             save_hlo: bool = False) -> Dict[str, Any]:
    """Lower + compile one cell in-process and return the artifact dict."""

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.lm_planner import plan_lm
    from repro.launch import serve as serve_mod
    from repro.launch import train as train_mod
    from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
    from repro.launch.mesh import make_production_mesh, mesh_spec_of
    from repro.models import lm
    from repro.models.common import SHAPES
    from repro.models.registry import (
        cell_is_applicable,
        get_config,
        input_specs,
    )

    t0 = time.time()
    cfg = get_config(arch)
    ok, why = cell_is_applicable(cfg, shape)
    name = _cell_name(arch, shape, mesh_kind, variant)
    if not ok:
        return {"cell": name, "status": "skipped", "reason": why,
                "arch": arch, "shape": shape, "mesh": mesh_kind,
                "variant": variant}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    mesh_spec = mesh_spec_of(mesh)
    plan = plan_lm(cfg, shape, mesh_spec, overrides=overrides)
    cfg = plan.cfg
    shp = SHAPES[shape]
    kind = shp["kind"]

    def sharded_struct(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings,
        )

    if kind == "train":
        step, state_sh, bsh = train_mod.build_train_step(plan, mesh)
        optimizer = train_mod.make_optimizer(plan)
        params_abs = lm.abstract_params(cfg)
        opt_abs = jax.eval_shape(lambda: optimizer.init(params_abs))
        state_abs = {
            "params": sharded_struct(params_abs, state_sh["params"]),
            "opt": sharded_struct(opt_abs, state_sh["opt"]),
            "step": jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=state_sh["step"]),
        }
        batch_abs = input_specs(cfg, shape)
        batch_abs = sharded_struct(batch_abs, bsh(batch_abs))
        lowered = step.lower(state_abs, batch_abs)
    elif kind == "prefill":
        pre, p_sh = serve_mod.build_prefill_step(plan, mesh, shp["seq"])
        params_abs = sharded_struct(lm.abstract_params(cfg), p_sh)
        batch_abs = input_specs(cfg, shape)
        batch_abs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=train_mod.batch_shardings(a, mesh)),
            batch_abs,
        )
        lowered = pre.lower(params_abs, batch_abs)
    else:  # decode
        dec, p_sh, c_sh = serve_mod.build_decode_step(plan, mesh)
        params_abs = sharded_struct(lm.abstract_params(cfg), p_sh)
        specs = input_specs(cfg, shape)
        B = specs["token"].shape[0]
        cache_abs = sharded_struct(
            specs["cache"], c_sh(B, shp["seq"])
        )
        token = jax.ShapeDtypeStruct(
            (B, 1), jnp.int32,
            sharding=train_mod.batch_shardings(specs["token"], mesh),
        )
        pos = jax.ShapeDtypeStruct((), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        lowered = dec.lower(params_abs, cache_abs, token, pos)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    n_dev = mesh_spec.n_devices
    pod_stride = (
        mesh_spec.size("data") * mesh_spec.size("model")
        if mesh_spec.size("pod") > 1 else 0
    )
    census = analyze_hlo(hlo, n_dev, pod_stride)
    terms = roofline_terms(census, n_dev, raw_cost=ca)

    artifact = {
        "cell": name,
        "status": "ok",
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "variant": variant,
        "kind": kind,
        "n_devices": n_dev,
        "plan": {
            "zero": plan.zero,
            "fsdp": plan.rules.fsdp,
            "expert_parallel": plan.rules.expert_parallel,
            "remat": plan.remat,
            "microbatches": plan.microbatches,
            "param_dtype": cfg.param_dtype,
            "m_dtype": plan.m_dtype,
            "v_dtype": plan.v_dtype,
            "notes": list(plan.notes),
        },
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_estimate": (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            ),
        },
        "cost": {
            "flops_per_device": census.dot_flops,
            "bytes_per_device": census.bytes_accessed,
            "xla_flops_uncorrected": ca.get("flops", 0.0),
            "xla_bytes_uncorrected": ca.get("bytes accessed", 0.0),
            "while_trips": census.while_trips,
        },
        "collectives": {
            "by_type_bytes": census.by_type_bytes,
            "by_type_count": census.by_type_count,
            "ici_link_bytes": census.ici_link_bytes,
            "dcn_link_bytes": census.dcn_link_bytes,
            "total_operand_bytes": census.total_operand_bytes,
        },
        "roofline": terms,
        "timings": {"lower_s": t_lower, "compile_s": t_compile},
    }
    if save_hlo:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        with open(os.path.join(ARTIFACT_DIR, name + ".hlo.txt"), "w") as f:
            f.write(hlo)
    return artifact


def _save(artifact: Dict[str, Any]) -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    path = os.path.join(ARTIFACT_DIR, artifact["cell"] + ".json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1, default=float)
    return path


def _run_all(mesh_kinds, force: bool, jobs_filter=None) -> int:
    from repro.models.registry import ARCH_IDS

    from repro.models.common import SHAPES

    failures = 0
    cells = [
        (a, s, m)
        for a in ARCH_IDS
        for s in SHAPES
        for m in mesh_kinds
    ]
    if jobs_filter:
        cells = [c for c in cells if jobs_filter(*c)]
    for arch, shape, mesh_kind in cells:
        name = _cell_name(arch, shape, mesh_kind)
        out = os.path.join(ARTIFACT_DIR, name + ".json")
        if os.path.exists(out) and not force:
            print(f"[skip cached] {name}")
            continue
        print(f"[run] {name}", flush=True)
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", arch, "--shape", shape, "--mesh", mesh_kind],
            capture_output=True, text=True,
            env={**os.environ, "PYTHONPATH": "src"},
            cwd=os.path.dirname(os.path.dirname(ARTIFACT_DIR)),
        )
        dt = time.time() - t0
        if proc.returncode != 0:
            failures += 1
            print(f"[FAIL {dt:.0f}s] {name}\n{proc.stdout[-2000:]}"
                  f"\n{proc.stderr[-4000:]}")
            with open(os.path.join(ARTIFACT_DIR, name + ".err.txt"),
                      "w") as f:
                f.write(proc.stdout + "\n" + proc.stderr)
        else:
            print(f"[ok {dt:.0f}s] {name}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="plan override key=value (e.g. microbatches=4)")
    args = ap.parse_args()

    mesh_kinds = (
        ("single", "multi") if args.mesh == "both" else (args.mesh,)
    )
    if args.all:
        return 1 if _run_all(mesh_kinds, args.force) else 0

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    overrides: Dict[str, Any] = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    artifact = run_cell(
        args.arch, args.shape, mesh_kinds[0],
        variant=args.variant, overrides=overrides or None,
        save_hlo=args.save_hlo,
    )
    path = _save(artifact)
    if artifact["status"] == "ok":
        r = artifact["roofline"]
        print(f"cell={artifact['cell']}")
        print(f"  memory/device: "
              f"args={artifact['memory']['argument_bytes']/2**30:.2f}GiB "
              f"temp={artifact['memory']['temp_bytes']/2**30:.2f}GiB "
              f"peak~{artifact['memory']['peak_hbm_estimate']/2**30:.2f}GiB")
        print(f"  flops/device={artifact['cost']['flops_per_device']:.3e} "
              f"bytes/device={artifact['cost']['bytes_per_device']:.3e}")
        print(f"  roofline: compute={r['compute_s']*1e3:.3f}ms "
              f"memory={r['memory_s']*1e3:.3f}ms "
              f"collective={r['collective_s']*1e3:.3f}ms "
              f"dominant={r['dominant']}")
        print(f"  artifact: {path}")
        return 0
    print(f"cell={artifact['cell']} SKIPPED: {artifact['reason']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
