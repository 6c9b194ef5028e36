"""Compile-cache placement (charon_tpu/jaxcache.py): the directory is
placed from outside through JAX_COMPILATION_CACHE_DIR, and the tuner
profile follows it."""

from __future__ import annotations

import os

import pytest

from charon_tpu import jaxcache


class _FakeConfig:
    def __init__(self):
        self.updates = {}

    def update(self, key, value):
        self.updates[key] = value


class _FakeJax:
    def __init__(self):
        self.config = _FakeConfig()


@pytest.fixture
def configured_dir_restored():
    before = jaxcache._CONFIGURED_DIR
    yield
    jaxcache._CONFIGURED_DIR = before


@pytest.mark.parametrize("cpu", [True, False], ids=["cpu", "tpu"])
def test_env_places_the_cache_for_every_platform(
    monkeypatch, tmp_path, configured_dir_restored, cpu
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jaxcache.cache_dir(cpu) == str(tmp_path)
    fake = _FakeJax()
    assert jaxcache.configure(fake, cpu=cpu) == str(tmp_path)
    # jax reads the variable itself: no directory is set in code, the
    # min-compile-time setting stays
    assert "jax_compilation_cache_dir" not in fake.config.updates
    assert "jax_persistent_cache_min_compile_time_secs" in fake.config.updates
    assert jaxcache.cache_stats()["dir"] == str(tmp_path)


def test_unset_env_keeps_the_fixed_paths_inside_the_checkout(
    monkeypatch, configured_dir_restored
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shared = os.path.join(repo, ".jax_cache")
    assert jaxcache.cache_dir(False) == shared
    assert jaxcache.cache_dir(True) == os.path.join(
        shared, "cpu-" + jaxcache.host_fingerprint()
    )
    fake = _FakeJax()
    jaxcache.configure(fake, cpu=False)
    assert fake.config.updates["jax_compilation_cache_dir"] == shared


def test_tuner_profile_follows_the_cache_dir(monkeypatch, tmp_path):
    from charon_tpu.core import autotune

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert autotune.default_profile_path().parent == tmp_path
