"""Where JAX keeps its persistent compilation cache.

Every entry point (``graph_run``, ``graph_serve``, ``benchmarks/run.py``,
``chip_smoke.py``) calls :func:`enable` once at start-up, so a second
run from the same checkout reuses the first run's compiled programs.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/compile_cache.py -> src/repro -> src -> root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and nothing is set in code. Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    what makes a later run find the entries again."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
