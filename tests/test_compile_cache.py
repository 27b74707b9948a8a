"""Placement of JAX's persistent compilation cache by the entry points.
The cache itself stays off in the tests: ``jax.config.update`` is
replaced by a recorder."""
import os

import jax
import pytest

from repro.compile_cache import CACHE_ENV, enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_environment_directory_is_honoured(monkeypatch, updates, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; only the threshold is set
    assert updates == [("jax_persistent_cache_min_compile_time_secs", 0)]


@pytest.mark.parametrize("value", [None, ""])
def test_default_is_the_fixed_checkout_directory(monkeypatch, updates,
                                                 value):
    if value is None:
        monkeypatch.delenv(CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(CACHE_ENV, value)
    expect = os.path.join(ROOT, ".jax_cache")
    assert enable_compile_cache() == expect
    assert updates == [("jax_persistent_cache_min_compile_time_secs", 0),
                       ("jax_compilation_cache_dir", expect)]
