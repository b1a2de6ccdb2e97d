"""The persistent compilation cache helper: an env directory wins; otherwise
one fixed, git-ignored directory inside the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_directory_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    # JAX reads the variable itself: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable()
    assert compile_cache.enable() == first
    assert jax.config.jax_compilation_cache_dir == first
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), "cache dir not git-ignored"
