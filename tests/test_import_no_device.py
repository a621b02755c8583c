"""Importing the engine touches no device.

A process that has initialised a JAX backend holds the chip, so a launcher
that imports ``repro`` and then starts a child that needs the chip would
leave the child without it.  The check runs in a fresh interpreter: this
test process has long since initialised its own backend.
"""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

MODULES = [
    "repro.core.pregel",
    "repro.core.executor",
    "repro.core.imru",
    "repro.core.serving",
    "repro.launch.query_serve",
    "repro.launch.mesh",
    "repro.launch.compile_cache",
]


@pytest.mark.parametrize("module", MODULES)
def test_import_initialises_no_backend(module):
    code = (
        f"import {module}\n"
        "from jax._src import xla_bridge\n"
        "print(xla_bridge.backends_are_initialized())\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (SRC, env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "False"
