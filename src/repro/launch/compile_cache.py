"""JAX's persistent compilation cache for the programs this repo ships.

``chip_smoke.py``, ``benchmarks/run.py`` and ``repro.launch.dryrun`` call
:func:`enable_compile_cache` before they compile anything, so a second run
of the same program on the same machine reads its compiled executables
back instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed.  Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache``: one path for every run, so later runs find what
    earlier ones wrote."""

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
