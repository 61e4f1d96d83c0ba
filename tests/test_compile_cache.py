"""Where the persistent compilation cache goes."""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.use_compile_cache()
    root = compile_cache.DEFAULT_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
