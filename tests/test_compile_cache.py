"""Where the entry points keep JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_set_env_dir_is_honoured_and_nothing_is_set(monkeypatch,
                                                    cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_env_uses_fixed_repo_dir(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").is_file()
    # a second call (a second run) lands on the same directory
    assert compile_cache.use_compile_cache() == got
