"""Where the persistent compilation cache goes.  JAX's config is replaced by
a recorder, so no test turns the cache on."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def updates(monkeypatch):
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_environment_variable_wins_and_nothing_is_set(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert updates == []


def test_default_is_the_checkout_cache_dir(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "chip_smoke.py").is_file()     # the checkout's root
    assert updates == [("jax_compilation_cache_dir", path)]
