"""Where the persistent compilation cache goes (launch/compile_cache.py)."""

import pathlib

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_env_directory_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_directory_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(ROOT / ".jax_cache") == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
