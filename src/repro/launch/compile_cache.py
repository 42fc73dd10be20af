"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself;
otherwise the cache lives at one fixed path inside the checkout
(``<repo>/.jax_cache``, ignored by git).  The path is part of the cache's
key, so it is never built from a temporary directory, a pid or the time.
``chip_smoke.py`` and the launchers call :func:`enable` once, before their
first compile.
"""

from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "enable"]

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
