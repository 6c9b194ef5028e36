"""Test configuration: force an 8-device virtual CPU mesh.

The driver benches on one real TPU chip, but multi-chip sharding must be
validated somewhere: we follow the reference's simnet-in-one-process strategy
(ref: testutil/integration/simnet_test.go) by running all sharding tests on a
virtual 8-device CPU mesh (xla_force_host_platform_device_count).

Platform pinning: tests never touch an accelerator (a chip belongs to
one process at a time, and the driver runs the suite on several workers),
so the platform is pinned to cpu through the env var before jax is
imported AND through jax.config after; that wins because no backend has
been initialized yet at conftest time.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    # Canonical flag string — EXACTLY the one __graft_entry__.dryrun_multichip
    # uses — so pytest and the driver dryrun share persistent-cache entries
    # for the same programs. Optimization level 0: tests assert
    # correctness, not speed, and XLA:CPU compile of the pairing programs
    # is severalfold faster without the LLVM optimization pipeline.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8"
        " --xla_backend_optimization_level=0"
    )
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the batched crypto kernels take minutes to
# compile on CPU; cache them across pytest processes. Host-fingerprinted
# dir (charon_tpu/jaxcache.py): XLA:CPU AOT entries are not portable
# across machines — a foreign-host cache is worse than a cold one.
from charon_tpu import jaxcache

jaxcache.configure(jax, cpu=True)
# NOTE on the persistent-cache segfault (CI.md "Known environment
# flake"): a fresh LARGE-program compile landing late in this
# program-heavy process can segfault jaxlib — in the cache write OR in
# backend_compile_and_load itself (both observed 2026-07-31/08-01), so
# suppressing writes here would not help and would leave non-isolated
# files permanently cold. The containment is structural instead: every
# known compile-heavy test body runs in a fresh subprocess
# (tests/isolation_util.py); if a future kernel change makes another
# in-process file's big program cold and it starts crashing the tier,
# isolate that file the same way.


# -- global-state hygiene (ISSUE 2 satellite: the silenced-node tracker
# regression reproduced only in full-suite runs — a CLI test leaving
# featureset flags behind flips the flag-selected AggSigDB for every
# later simnet build). Snapshot + restore the feature registry and the
# tbls backend around EVERY test so suite order can never leak state.

import pytest as _pytest


@_pytest.fixture(autouse=True)
def _isolate_process_globals():
    from charon_tpu import tbls as _tbls
    from charon_tpu.app import faultinject as _fi
    from charon_tpu.app import featureset as _fs

    fs_state = (_fs._min_status, set(_fs._enabled), set(_fs._disabled))
    tbls_impl = _tbls._current
    fi_plane = _fi._plane
    yield
    _fs._min_status, _fs._enabled, _fs._disabled = fs_state
    _tbls._current = tbls_impl
    _fi._plane = fi_plane


# -- thread/task leak guard (ISSUE 10 satellite) -----------------------------
#
# The host-plane/chaos/cryptoplane suites spawn the system's real
# concurrency (decode pools, device lanes, warm-up workers, dispatcher
# tasks); a scenario that forgets close() leaks an idle executor thread
# per test, and a task leaked past its asyncio.run surfaces only as an
# easy-to-miss "Task was destroyed but it is pending!" stderr line.
# Snapshot threads before each guarded test, and fail the TEST on
# either signal (charon_tpu/analysis/sanitizer.py primitives).

_LEAK_GUARDED_FILES = {
    "test_hostplane.py",
    "test_chaos_scenarios.py",
    "test_cryptoplane.py",
}


@_pytest.fixture(autouse=True)
def _thread_task_leak_guard(request):
    fspath = getattr(request.node, "fspath", None)
    name = fspath.basename if fspath is not None else ""
    if name not in _LEAK_GUARDED_FILES:
        yield
        return
    from charon_tpu.analysis import sanitizer as _san

    before = _san.thread_snapshot()
    watcher = _san.TaskDestroyedWatcher().install()
    yield
    destroyed = watcher.uninstall()
    leaked = _san.check_thread_leaks(before, grace=5.0)
    problems = []
    if leaked:
        problems.append(
            f"leaked thread(s): {leaked} — an executor/worker outlived "
            "the test (missing close()/shutdown())"
        )
    if destroyed:
        problems.append(
            f"{len(destroyed)} asyncio task(s) destroyed while pending "
            f"(leaked past their loop): {destroyed[:3]}"
        )
    if problems:
        _pytest.fail("; ".join(problems))
