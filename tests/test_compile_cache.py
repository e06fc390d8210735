"""Where the persistent compilation cache lives: the directory named by
`JAX_COMPILATION_CACHE_DIR` when it is set, else one fixed, git-ignored
path in the checkout."""
import os
import subprocess

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch,
                                              restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert cache.use_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_a_fixed_ignored_path_in_the_checkout(monkeypatch,
                                                         restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = cache.use_compile_cache()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert got == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert cache.use_compile_cache() == got          # same path every call
    os.makedirs(got, exist_ok=True)                  # as JAX's first write
    ignored = subprocess.run(["git", "check-ignore", "-q", got], cwd=root)
    assert ignored.returncode in (0, 128)            # 128: not a git checkout
