"""The persistent compilation cache (repro.utils.compile_cache): its
directory is ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
else the fixed, git-ignored ``<repo>/.jax_cache``; its keys hold the
programs' metadata, so an executable cached without the round's named
scopes is never loaded for a program that has them."""
import os
import re
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


_FLAGS = ("jax_compilation_cache_dir",
          "jax_compilation_cache_include_metadata_in_key",
          "jax_hlo_source_file_canonicalization_regex")


def test_default_cache_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = {flag: getattr(jax.config, flag) for flag in _FLAGS}
    try:
        assert use_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex,
                      "", str(REPO / "src" / "x.py")) == "src/x.py"
    finally:
        for flag, value in was.items():
            jax.config.update(flag, value)
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


# Fill the cache from the round program with its named scopes taken out,
# then compile the scoped program afresh: the two differ only in op
# metadata, so a key without it would load the first and lose the scopes.
_STALE = """
import contextlib, re
import jax, numpy as np
from repro.core import FedAvgConfig, RoundEngine
from repro.models import mnist_2nn
from repro.utils.compile_cache import use_compile_cache
use_compile_cache()

def round_text():
    model = mnist_2nn(n_classes=5, d_in=12)
    rng = np.random.default_rng(0)
    clients = [(rng.normal(size=(n, 12)).astype(np.float32),
                rng.integers(0, 5, n).astype(np.int32)) for n in (9, 24, 17)]
    eng = RoundEngine(model.loss, model.init(jax.random.PRNGKey(0)), clients,
                      FedAvgConfig(C=0.5, E=1, B=8, lr=0.1, seed=3))
    text = eng.lower_round(1).compile().as_text()
    return len(re.findall(r'op_name="[^"]*fedavg[.]', text))

scoped = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
print("unscoped", round_text())
jax.named_scope = scoped
jax.clear_caches()
print("scoped", round_text())
"""


def test_cached_program_without_the_scopes_is_not_reused(tmp_path):
    target = tmp_path / "cache"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(target),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=str(REPO / "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", _STALE], env=env, capture_output=True,
        text=True, timeout=300, check=True,
    )
    counts = dict(line.split() for line in out.stdout.splitlines())
    assert counts["unscoped"] == "0"
    assert int(counts["scoped"]) > 0
    assert any(target.iterdir())
