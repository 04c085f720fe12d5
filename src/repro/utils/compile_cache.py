"""Where JAX's persistent compilation cache lives, and what its keys hold.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`use_compile_cache` once, before their first compile; library modules
never do, so importing ``repro`` changes no JAX configuration.

A cache entry's key includes nothing about its directory, but a directory
that moves between runs never hits, so the default is a fixed path inside
the checkout: ``<repo>/.jax_cache`` (git-ignored). Where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it at start-up and
that directory wins.

Either way the key includes the program's metadata. JAX leaves it out by
default, so a program that differs from a cached one only in its
``jax.named_scope`` names (each op's ``op_name``) would load the cached
executable with the old names, and a profile of it would put every op under
the old scopes. Source paths in that metadata are taken relative to the
checkout, so the same tree hits the same entries wherever it lies.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache, keyed on the programs'
    metadata too, and return its directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else :data:`DEFAULT_CACHE_DIR`."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(REPO_ROOT) + os.sep))
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
