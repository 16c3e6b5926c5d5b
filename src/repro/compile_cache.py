"""Where JAX keeps its persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at one fixed
path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``).  The path is part of what lets a later process find a
cached program again, so it is never built from a temporary name, a pid
or the time.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`configure` first thing; importing this
module changes nothing.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".jax_cache"))


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
