"""The persistent-compilation-cache helper of the entry points."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_helper_sets_nothing(monkeypatch, tmp_path,
                                              restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = cache.enable_compile_cache()
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.enable_compile_cache() == path     # stable across calls


_HITS = """
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e)
    if e == "/jax/compilation_cache/cache_hits" else None)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
print("HITS", len(hits))
"""


def test_second_run_hits_env_var_cache(tmp_path):
    """Two processes with ``JAX_COMPILATION_CACHE_DIR`` set: the first
    fills that directory, the second compiles nothing it already has."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"),
               **{cache.ENV_VAR: str(tmp_path / "cc")})
    hits = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _HITS], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        hits.append(int(out.stdout.split("HITS")[-1]))
    assert hits[0] == 0 and hits[1] > 0, hits
    assert os.listdir(tmp_path / "cc")
