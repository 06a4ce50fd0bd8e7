"""Where the entry points keep JAX's persistent compilation cache.

``python chip_smoke.py``, ``python -m repro.launch.train`` and
``python -m repro.serving.service`` call :func:`enable_compile_cache`
before their first compile, so a second run on the same machine loads
its executables instead of compiling them again:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
    set in code.
  * otherwise: the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
    since the path is part of what makes a later run find an entry.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    (see module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
