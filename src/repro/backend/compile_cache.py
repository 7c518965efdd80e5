"""JAX's persistent compilation cache, placed from outside or at one fixed
path.

A cold process compiles every kernel, sort and query artifact again; on the
chip that is minutes. The persistent cache keeps the compiled executables
on disk, and a later process finds them only at the same path, so the path
never moves between runs: it is never built from a temp name, a process id
or a time.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself and
  this module sets no other.
* Otherwise: ``<repo>/.jax_cache``, next to ``src/`` (listed in
  ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where compiled executables persist: the environment's directory if
    one is given, else the repository's fixed ``.jax_cache``."""
    return os.environ.get(ENV_DIR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir`; → the
    directory. Call before the first compile."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
