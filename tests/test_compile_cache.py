"""The launchers' persistent compilation cache: JAX's own variable wins,
otherwise one fixed, git-ignored directory inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the process-wide cache setting after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_leaves_jax_setting_alone(monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_ignored_dir_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: no temp name, pid or time in it
    assert compile_cache.enable() == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
