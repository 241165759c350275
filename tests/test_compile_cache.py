"""The entry points' persistent compilation cache: it goes where
JAX_COMPILATION_CACHE_DIR says when that is set, and otherwise to one
fixed directory inside the checkout."""
import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_env_dir_is_used_and_written(monkeypatch, tmp_path,
                                     restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    # jax reads the variable itself when it is imported; mirror that
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # a fresh shape, so this process has not compiled it before
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(
        jnp.arange(37, dtype=jnp.int32)))
    assert any(tmp_path.iterdir()), "nothing cached in the env dir"


def test_default_dir_is_fixed_inside_checkout(monkeypatch,
                                              restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
