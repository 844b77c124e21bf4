"""Where the entry points keep the persistent compile cache."""
import os
import subprocess

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_wins_and_nothing_is_set(monkeypatch, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_and_gitignored(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    if os.path.isdir(os.path.join(REPO, ".git")):
        ignored = subprocess.run(["git", "-C", REPO, "check-ignore", "-q",
                                  os.path.join(path, "entry")])
        assert ignored.returncode == 0
