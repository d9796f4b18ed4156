"""Where JAX keeps its persistent compilation cache.

Compiling the serving programs at a model's published widths takes tens
of seconds each; the persistent cache lets the next process load them
instead. Its directory is part of each entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), else
one fixed directory inside the checkout, listed in ``.gitignore``.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory. Call before the first compile."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
