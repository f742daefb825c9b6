"""The entry points' persistent-compile-cache helper picks a fixed
directory: ``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's
``.jax_cache``.  JAX's own config is observed, never changed, here."""
import jax
import pytest

from repro.launch import cache


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert cache.enable_compile_cache() == "/cache/from/env"
    assert updates == []


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = cache.enable_compile_cache()
    assert first == cache.enable_compile_cache()
    assert first == str(cache.CHECKOUT / ".jax_cache")
    assert (cache.CHECKOUT / "src" / "repro").is_dir()
    assert updates == [("jax_compilation_cache_dir", first)] * 2
