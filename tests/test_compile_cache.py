"""The persistent compile cache every entry point enables
(repro/launch/compile_cache.py): JAX_COMPILATION_CACHE_DIR when set,
otherwise one fixed directory inside the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_names_the_cache_dir(monkeypatch, tmp_path,
                                     restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_the_fixed_path_in_the_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    checkout = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert path == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path   # stable
