"""Where the persistent compilation cache goes (utils/devices.py): the
directory JAX_COMPILATION_CACHE_DIR names, untouched, or <checkout>/.jax_cache."""

import os

import jax
import pytest

from open_simulator_tpu.utils import devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh(monkeypatch):
    """A process that has not yet placed the cache; config restored after."""
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(devices, "_cache_enabled", False)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(fresh, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))  # as JAX read it
    devices.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_unset_env_uses_the_checkout(fresh, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    devices.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    assert devices.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    devices.enable_compilation_cache()  # idempotent
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
