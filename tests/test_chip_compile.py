"""The main path's Pallas kernels, compiled for a DESCRIBED TPU v5e.

The sandbox and CI have no chip, but the TPU compiler is installed and
compiles for a topology that is described, not attached — so these
cases guard what interpret-mode tests cannot see: Mosaic accepting the
kernels at their real tile width, and a `pallas_call` tracing under
`jax.shard_map(check_vma=True)` (every plane program is one), on a
one-device and on a four-device mesh. Nothing runs; a compile that
passes is not a chip run.

The topology is described inside a module-scoped fixture — never at
import, never in a skipif/parametrize argument: only one process may
load the TPU library, and under pytest-xdist every worker imports every
test file. The compile happens in the test's own process for the same
reason, and the cases stay in this one file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from charon_tpu.ops import limb
from charon_tpu.ops import pallas_mont as PK


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-device executable is written to the persistent cache
    but cannot be read back without a chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _rows(ctx, sharding, n=1):
    spec = jax.ShapeDtypeStruct(
        (PK.TILE, ctx.n_limbs), jnp.uint32, sharding=sharding
    )
    return (spec,) * n


def _assert_kernel_in(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "ctx,mxu",
    [(limb.FP32, False), (limb.FR32, False), (limb.FP32, True)],
    ids=["fp", "fr", "fp-mxu"],
)
def test_mont_mul_pallas_compiles_for_v5e(topo, no_compile_cache, ctx, mxu):
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn = jax.jit(functools.partial(PK.mont_mul_pallas, ctx, mxu=mxu))
    _assert_kernel_in(fn.lower(*_rows(ctx, one_chip, 2)).compile())


@pytest.mark.parametrize("n_devices", [1, 4])
def test_mont_mul_pallas_under_shard_map(topo, no_compile_cache, n_devices):
    """The case the installed JAX refused: a pallas_call inside
    shard_map(check_vma=True) must declare its outputs' varying axes."""
    ctx = limb.FP32
    mesh = Mesh(topo.devices[:n_devices], ("shards",))
    sharded = jax.jit(
        jax.shard_map(
            functools.partial(PK.mont_mul_pallas, ctx),
            mesh=mesh,
            in_specs=(P("shards"), P("shards")),
            out_specs=P("shards"),
        )
    )
    rows = jax.ShapeDtypeStruct(
        (PK.TILE * n_devices, ctx.n_limbs),
        jnp.uint32,
        sharding=NamedSharding(mesh, P("shards")),
    )
    _assert_kernel_in(sharded.lower(rows, rows).compile())


def test_fp2_sqr_pallas_compiles_for_v5e(topo, no_compile_cache):
    # fp2_mul_pallas (same building blocks, three Montgomery cores) is
    # left out: 40 s alone and 90 s beside five other workers, enough to
    # starve the suite's timing-sensitive simnet tests
    ctx = limb.FP32
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn = jax.jit(lambda a0, a1: PK.fp2_sqr_pallas(ctx, (a0, a1)))
    _assert_kernel_in(fn.lower(*_rows(ctx, one_chip, 2)).compile())
