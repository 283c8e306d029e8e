"""Where entry points put JAX's persistent compilation cache."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE, use_compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
    # a fixed path at the checkout root: no temp name, pid or time in it
    assert CHECKOUT_CACHE == Path(__file__).resolve().parents[1] / ".jax_cache"


def test_environment_setting_stands(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", "/elsewhere/jax-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    assert use_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == "/elsewhere/jax-cache"
