"""bench_common.init_jax is strict: a benchmark measures the chip or it
fails — the only way onto the CPU is the caller's own JAX_PLATFORMS=cpu
(the --smoke correctness gates in ci.sh)."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench_common  # noqa: E402
from charon_tpu import jaxcache  # noqa: E402


@pytest.fixture
def no_cache_reconfigure(monkeypatch):
    # the test process's real cache placement (conftest) stays untouched
    calls = []
    monkeypatch.setattr(
        jaxcache, "configure", lambda jax_mod, *, cpu: calls.append(cpu)
    )
    return calls


def test_init_jax_raises_without_a_tpu(monkeypatch, no_cache_reconfigure):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="no TPU"):
        bench_common.init_jax()
    assert no_cache_reconfigure == [False]


def test_init_jax_accepts_the_callers_own_cpu_pin(
    monkeypatch, no_cache_reconfigure
):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    jax = bench_common.init_jax()
    assert jax.devices()[0].platform == "cpu"
    assert no_cache_reconfigure == [True]
