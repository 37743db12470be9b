"""Where ``repro.launch.compile_cache.enable`` puts JAX's persistent cache."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache

_OPTIONS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    prev = {name: getattr(jax.config, name) for name in _OPTIONS}
    yield
    for name, value in prev.items():
        jax.config.update(name, value)
    cc.reset_cache()


def test_env_dir_is_left_to_jax(tmp_path, monkeypatch, restore_cache_config):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv(compile_cache.ENV, env_dir)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable(tmp_path / "repo") == env_dir
    # JAX reads the variable itself; the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "repo" / ".jax_cache").exists()


def test_unset_env_uses_fixed_path_in_checkout(tmp_path, monkeypatch,
                                               restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable(tmp_path)
    assert path == str(tmp_path.resolve() / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: a cache is only found where it was left
    assert compile_cache.enable(tmp_path) == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
