"""Persistent XLA compile cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, this module
changes nothing.  Otherwise :func:`use_compile_cache` keeps compiled
programs under the fixed ``<repo>/.jax_cache`` (gitignored), so the next run
of the same command from the same checkout finds them again.  Only entry
points call it (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) — never the library at import, never the tests.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
