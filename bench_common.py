"""Shared device init for the repo-root benchmarks.

Strict: a benchmark measures the chip or it fails. The compile cache is
configured, the backend is initialised, and anything but a TPU raises —
unless the CALLER pinned the process to the CPU itself with
`JAX_PLATFORMS=cpu`, which is what the `--smoke` correctness gates in
ci.sh do (their output names the platform; it is never a device number).
"""

from __future__ import annotations

import os


def init_jax():
    """Import jax, configure the persistent compile cache, initialise
    the backend. Returns the jax module; raises RuntimeError when the
    platform is not a TPU and the caller did not ask for the CPU."""
    import jax

    from charon_tpu import jaxcache

    cpu_asked = os.environ.get("JAX_PLATFORMS") == "cpu"
    jaxcache.configure(jax, cpu=cpu_asked)
    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu_asked:
        raise RuntimeError(
            f"no TPU: jax initialised platform {platform!r} — a benchmark "
            "does not fall back to another device (set JAX_PLATFORMS=cpu "
            "yourself for a --smoke correctness run)"
        )
    return jax
