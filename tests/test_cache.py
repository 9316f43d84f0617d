"""Where generated artifacts go: under ``.cache/`` of the checkout that
holds the code, whatever the working directory."""
from pathlib import Path

import jax
import pytest

from repro import cache
from repro.experiments import setup

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    saved = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_paths_anchor_to_the_checkout(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert cache.CHECKOUT == CHECKOUT
    assert Path(setup.CACHE_DIR) == CHECKOUT / ".cache" / "rar_system"


def test_compile_cache_defaults_to_the_checkout(monkeypatch, tmp_path,
                                                cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    path = cache.enable_compile_cache()
    assert Path(path) == CHECKOUT / ".cache" / "jax_compile"
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_sets_nothing(monkeypatch, tmp_path,
                                        cache_dir_restored):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
