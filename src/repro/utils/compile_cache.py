"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`use_compile_cache` once, before their first compile; library modules
never do, so importing ``repro`` changes no JAX configuration.

A cache entry's key includes nothing about its directory, but a directory
that moves between runs never hits, so the default is a fixed path inside
the checkout: ``<repo>/.jax_cache`` (git-ignored). Where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it at start-up and
that directory wins; nothing else is configured then.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
