"""The entry points' compile-cache placement (launch/compile_cache.py)."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_placement_wins_and_nothing_is_set(
        monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_path_otherwise(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.CHECKOUT_CACHE.parent.joinpath(
        "chip_smoke.py").is_file()
