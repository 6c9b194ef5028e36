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
# libgomp reads this once, when native/libcharon_native.so pulls it in:
# the native tbls backend's verify_batch is an OpenMP loop, and with the
# default (active) policy its idle team spins between calls — a 4-node
# simnet then burns four to five cores for one core's work (53 CPU-s in
# 12 s of wall, 13 with `passive`: my sandbox, PR 41) and six xdist
# workers starve each other's event loops.
os.environ.setdefault("OMP_WAIT_POLICY", "passive")

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
    # an owner that only a reference cycle keeps (a test that hangs its
    # own closure on the coalescer) is no leak, and whether the cyclic
    # collector ran inside the grace must not decide the test
    import gc

    gc.collect()
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


# -- one limit a test (ISSUE 41) ---------------------------------------------
#
# pytest-timeout is not installed, so the interpreter's own timer does
# it: an xdist worker runs its tests on its main thread, where SIGALRM's
# handler raises INTO whatever the test is doing (an asyncio.run, a
# subprocess.run, a lock). The test FAILS with every thread's stack on
# stderr and its worker goes on to the next test. The limit covers the
# test's set-up too (a module-scoped fixture that compiles in a child
# belongs to the first test that asks for it). A main thread held in C
# code that never comes back to the interpreter cannot be raised into:
# HARD_GRACE seconds later faulthandler's watchdog thread dumps the
# stacks and ends the worker, which costs xdist that one test and a new
# worker.

import faulthandler
import signal
import sys
import threading
import time
import traceback

TEST_LIMIT = 300.0  # seconds a test may take unless it says otherwise
# The `slow` tier is outside the driver's run (`-m 'not slow'`) and by its
# own description wall-clock heavy: its children compile for up to 75
# minutes cold (CI.md, "Round-5 slow-tier stabilization") under
# run_isolated timeouts of their own, up to 100 minutes.
SLOW_TEST_LIMIT = 7200.0
REFIRE = 5.0
HARD_GRACE = 60.0
TEARDOWN_LIMIT = 60.0  # what tear-down keeps when the limit is spent


class LimitExceeded(BaseException):
    """The test outlived its limit (`limit` marker, else TEST_LIMIT).
    Not an Exception: a retry loop's `except Exception` must not eat it."""


def _limit_of(item) -> float:
    marker = item.get_closest_marker("limit")
    if marker is not None:
        return float(marker.args[0])
    return SLOW_TEST_LIMIT if item.get_closest_marker("slow") else TEST_LIMIT


def _dump_all_stacks(out) -> None:
    names = {t.ident: t.name for t in threading.enumerate()}
    for ident, frame in sys._current_frames().items():
        print(f"--- thread {names.get(ident, ident)}:", file=out)
        traceback.print_stack(frame, file=out)


def _under_limit(item, seconds: float):
    """Run one phase of `item` (the hook's `yield`) under `seconds`."""
    if not hasattr(signal, "setitimer") or (
        threading.current_thread() is not threading.main_thread()
    ):
        yield
        return

    fired = []
    said = f"outlived its limit of {_limit_of(item):g} s"

    def on_alarm(signum, frame):
        if not fired:
            fired.append(True)
            print(
                f"\n{item.nodeid} {said}; every thread's stack:",
                file=sys.stderr,
            )
            _dump_all_stacks(sys.stderr)
        raise LimitExceeded(f"{said} (stacks on stderr)")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    # again every REFIRE seconds: raised into an asyncio task that is
    # not the main one, the exception ends that task and not the run
    signal.setitimer(signal.ITIMER_REAL, max(seconds, 0.001), REFIRE)
    faulthandler.dump_traceback_later(
        seconds + HARD_GRACE, exit=True, file=sys.__stderr__
    )
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


_limit_ends_at = _pytest.StashKey[float]()


def _left(item) -> float:
    return item.stash[_limit_ends_at] - time.monotonic()


@_pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    item.stash[_limit_ends_at] = time.monotonic() + _limit_of(item)
    yield from _under_limit(item, _left(item))


@_pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    yield from _under_limit(item, _left(item))


@_pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_teardown(item):
    yield from _under_limit(item, max(_left(item), TEARDOWN_LIMIT))


# -- longest first (ISSUE 41) ------------------------------------------------
#
# With `--dist loadfile` a run's wall is its slowest file plus the second
# at which that file started. The files whose tests carry the `limit`
# marker say themselves that they are long: they go to the head of the
# collection, the longest limit first. The marker is the only input.


def pytest_collection_modifyitems(items):
    longest: dict[str, float] = {}
    for item in items:
        marker = item.get_closest_marker("limit")
        if marker is not None:
            path = item.nodeid.split("::", 1)[0]
            longest[path] = max(longest.get(path, 0.0), float(marker.args[0]))
    # a stable sort: every other file keeps its place
    items.sort(key=lambda item: -longest.get(item.nodeid.split("::", 1)[0], 0.0))
