"""How the entry points set up JAX: where the compile cache goes, and when
the Pallas kernels run in the interpreter."""

import os

import jax
import pytest

from repro import compile_cache
from repro.kernels import ops
from repro.tune import cache


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable() == want          # the same on every call


@pytest.mark.parametrize("backend, interpret", [("tpu", False),
                                                ("cpu", True),
                                                ("gpu", True)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.interpret_mode() is interpret
    kind = jax.devices()[0].device_kind.lower()
    assert cache.device_kind() == (f"{kind}:interpret" if interpret
                                   else kind)
