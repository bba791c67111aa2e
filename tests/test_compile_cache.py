"""The compile-cache helper every entry point calls."""

import jax

from repro.launch import compile_cache


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    repo = __file__.rsplit("/tests/", 1)[0]
    assert first == f"{repo}/.jax_cache"
