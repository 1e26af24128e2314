"""Persistent XLA compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``)
and the CLI mains of ``launch/train.py`` and ``launch/serve.py`` call
``enable_compile_cache()`` once at start-up; importing a library module
never does. JAX keys cache entries by program and compiler, and the
directory must stay put for entries to be found again, so it is either
``JAX_COMPILATION_CACHE_DIR`` (read by JAX itself; nothing else is set
then) or one fixed directory inside the checkout, ``<repo>/.jax_cache``
(listed in ``.gitignore``).
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]
