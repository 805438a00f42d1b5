"""The entry points' compile-cache rule (``repro.utils.compile_cache``):
JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed directory
in the checkout.  Nothing here turns the cache on."""
import os

from repro.utils import compile_cache as cc


def test_env_var_leaves_the_directory_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert cc.compile_cache_dir() is None


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.compile_cache_dir() == os.path.join(root, ".jax_cache")
    assert cc.compile_cache_dir() == cc.compile_cache_dir()
