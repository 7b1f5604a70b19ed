"""launch/env.py: where the persistent compile cache lives."""
import os

import jax
import pytest

from repro.launch import env as launch_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def _restore_cache_dir():
    """configure() sets process-wide state: put it back afterwards."""
    was = jax.config.jax_compilation_cache_dir
    environ = dict(os.environ)
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    os.environ.clear()
    os.environ.update(environ)


def test_configure_honours_compile_cache_env(monkeypatch, tmp_path,
                                             _restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    resolved = launch_env.configure()
    assert resolved["compile_cache_dir"] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_configure_defaults_to_fixed_in_checkout_cache(monkeypatch,
                                                       _restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    resolved = launch_env.configure()
    want = os.path.join(REPO, ".jax_cache")
    assert resolved["compile_cache_dir"] == want
    assert jax.config.jax_compilation_cache_dir == want
    # fixed: the same path on every call, whatever the process
    assert launch_env.compile_cache_dir() == want
