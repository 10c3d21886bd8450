"""Placement of JAX's persistent compilation cache."""
import pathlib

import jax

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_environment_directory_wins_and_nothing_else_is_set(monkeypatch,
                                                            tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert enable_compile_cache() == first  # stable across calls
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert pathlib.Path(first) == CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
