"""JAX's persistent compilation cache, switched on by the entry points.

A cold process otherwise recompiles every serve bucket and every train
chunk length.  :func:`enable_compile_cache` is called from the ``main`` of
each entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/pareto.py``, ``benchmarks/run.py``) and never on import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory.  Otherwise the cache lives at one fixed path in
the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is part of the
cache key, so it never carries a temp name, a pid or a timestamp.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
