"""The compile-cache helper (repro.compile_cache): the environment
variable names the cache when set, and otherwise the cache sits at the
fixed, gitignored ``<checkout>/.jax_cache``."""

import pathlib

import jax
import pytest

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_jax_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_var_wins_and_sets_nothing(monkeypatch, tmp_path,
                                       restore_jax_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_in_checkout_path(monkeypatch,
                                           restore_jax_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path every call: the directory is part of the cache key
    assert compile_cache.compile_cache_dir() == want


def test_default_cache_dir_is_gitignored():
    lines = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in lines
