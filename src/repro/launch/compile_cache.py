"""JAX's persistent compilation cache at a path placed from outside.

Call :func:`enable_compile_cache` once at process entry (a launcher's
``main``, a worker's ``main``), never at import: tests and library users
keep JAX's own defaults.

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory is used, and no other
  is set.
* Unset: ``<checkout>/.jax_cache``, a fixed path (the cache key includes
  nothing that would change between runs of the same checkout).  The path
  is exported to ``os.environ`` so child processes (cluster workers)
  share it.
"""
from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at the placed directory; returns it."""
    path = os.environ.get(ENV)
    if not path:
        # this file is <checkout>/src/repro/launch/compile_cache.py
        root = os.path.abspath(__file__)
        for _ in range(4):
            root = os.path.dirname(root)
        path = os.path.join(root, ".jax_cache")
        os.environ[ENV] = path
    # JAX reads the variable when it is imported; a process that imported
    # it already is told directly.  One that never runs JAX stays off it.
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
