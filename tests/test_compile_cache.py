"""The persistent compilation cache's directory (repro.utils.compile_cache):
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the fixed,
git-ignored ``<repo>/.jax_cache``."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.utils.compile_cache import DEFAULT_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
import jax, jax.numpy as jnp
from repro.utils.compile_cache import use_compile_cache
print(use_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def test_default_cache_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_env_cache_dir_wins_and_is_written(tmp_path):
    """A compile under ``JAX_COMPILATION_CACHE_DIR`` lands there, and the
    helper leaves the repo's default directory untouched."""
    target = tmp_path / "cache"
    before = (sorted(os.listdir(DEFAULT_CACHE_DIR))
              if DEFAULT_CACHE_DIR.exists() else None)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(target),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=str(REPO / "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, capture_output=True,
        text=True, timeout=300, check=True,
    )
    assert out.stdout.split()[0] == str(target)
    assert target.is_dir() and any(target.iterdir())
    after = (sorted(os.listdir(DEFAULT_CACHE_DIR))
             if DEFAULT_CACHE_DIR.exists() else None)
    assert after == before
