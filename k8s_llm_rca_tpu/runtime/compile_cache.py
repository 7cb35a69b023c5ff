"""Where JAX's persistent compilation cache lives: decided outside the code.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module sets
nothing.  Unset: one fixed directory inside the checkout — the path is part
of the cache key, so a directory named after a pid, a time or a temp file
would never hit.  Entry points that compile real-size programs
(chip_smoke.py, sweeps/*) call ``enable_compile_cache`` once before their
first jit; the benchmark places its own (benchmarks/lib/build.py); tests
share one directory of their own outside the checkout (tests/conftest.py).
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the directory compiled programs are kept in."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
