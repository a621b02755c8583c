"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; callers (dryrun, launchers)
decide when devices are realized.  The single-pod mesh is 16 x 16 = 256
chips (data x model); the multi-pod mesh adds a leading pod axis:
2 x 16 x 16 = 512 chips.  Axis order puts ``pod`` outermost so consecutive
device ids share a pod — intra-pod collectives stay on ICI and the cross-pod
hop is the paper's 1-level aggregation tree over DCN.
"""

from __future__ import annotations

import os

import jax

__all__ = ["make_data_mesh", "make_production_mesh",
           "mesh_spec_of", "virtual_device_env",
           "SINGLE_POD_AXES", "MULTI_POD_AXES"]

SINGLE_POD_AXES = (("data", 16), ("model", 16))
MULTI_POD_AXES = (("pod", 2), ("data", 16), ("model", 16))


def make_data_mesh(n_data: int = 0):
    """Pure data-parallel mesh over the local devices — the layout of the
    sharded Pregel tests and the fig10 sharded semi-naive benchmark.
    ``n_data=0`` uses every visible device (e.g. 8 under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""

    if n_data <= 0:
        n_data = len(jax.devices())
    return jax.make_mesh(
        (n_data,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )


def virtual_device_env(n: int = 8, base_env=None) -> dict:
    """Environment for a subprocess that must see ``n`` virtual CPU devices.

    XLA reads ``--xla_force_host_platform_device_count`` at first jax
    import, so the flag only helps a *fresh* process — the sharded test
    programs and the fig10 ``--sharded`` self re-exec both launch
    subprocesses with this environment.  An already-present device-count
    flag is respected (the caller is running under one)."""

    env = dict(os.environ if base_env is None else base_env)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    return env


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_spec_of(mesh) -> "MeshSpec":
    from repro.core.hardware import MeshSpec

    return MeshSpec(
        tuple((n, int(s)) for n, s in zip(mesh.axis_names, mesh.devices.shape))
    )
