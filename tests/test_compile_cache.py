"""The persistent compilation cache is placed from outside the program."""
import os

import jax

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_env_wins_else_fixed_in_checkout(monkeypatch, tmp_path):
    # JAX_COMPILATION_CACHE_DIR wins, and nothing is set in code: JAX
    # reads the variable itself
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # without it: one fixed, git-ignored path inside the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    paths = {compile_cache.compile_cache_dir() for _ in range(3)}
    assert paths == {os.path.join(ROOT, ".jax_cache")}
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
