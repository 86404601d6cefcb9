"""Where the persistent compile cache goes: the caller's directory when
JAX_COMPILATION_CACHE_DIR is set, else one fixed path in the checkout."""
import os
from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_DIR, ENV_VAR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def live_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_alone(monkeypatch, tmp_path, live_cache_dir):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert os.environ[ENV_VAR] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing else set


def test_default_dir_is_fixed_in_checkout(monkeypatch, live_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache") == str(DEFAULT_DIR)
    assert os.environ[ENV_VAR] == path  # inherited by child processes
    assert jax.config.jax_compilation_cache_dir == path
    monkeypatch.delenv(ENV_VAR)
    assert enable_compile_cache() == path  # the same path on every call
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
