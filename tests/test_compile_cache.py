"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import jax
import pytest

from repro.compile_cache import CACHE_DIR, use_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_cache_dir_from_env_else_fixed_path(env, monkeypatch, restore_cache_dir):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    where = use_compile_cache()
    if env is None:
        # one fixed, git-ignored path in the checkout
        assert where == str(CACHE_DIR) == jax.config.jax_compilation_cache_dir
        assert (CACHE_DIR.parent / "src" / "repro" / "compile_cache.py").exists()
        assert ".jax_cache/" in (CACHE_DIR.parent / ".gitignore").read_text().split()
    else:
        # the environment's cache is JAX's own business: nothing set in code
        assert where == env
        assert jax.config.jax_compilation_cache_dir == before
