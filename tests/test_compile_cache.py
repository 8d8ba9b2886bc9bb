"""The compile cache is placed from outside: with
JAX_COMPILATION_CACHE_DIR set the program configures nothing and writes
nowhere else; unset, it uses one fixed directory in the checkout."""

import os

import jax
import pytest

from mmlspark_tpu.serving import aot
from mmlspark_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_env_var_wins_and_nothing_is_configured(monkeypatch, tmp_path,
                                                config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    art = tmp_path / "artifact"
    art.mkdir()
    with aot._artifact_cache(str(art)):
        pass
    assert config_updates == []
    assert not (art / "xla_cache").exists()


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch,
                                                      config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", path)]


def test_artifact_cache_redirects_and_restores(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    with aot._artifact_cache(str(tmp_path)):
        assert jax.config.jax_compilation_cache_dir == \
            str(tmp_path / "xla_cache")
    assert jax.config.jax_compilation_cache_dir == before
