"""The two mechanisms that end a tier-1 run (ISSUE 41), tested themselves:
the one bounded wait of `charon_tpu/testutil/waiting.py` on a still clock,
the limit a test has in `tests/conftest.py` and `run_isolated`'s timeout in
children of their own."""

import asyncio
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from charon_tpu.testutil import waiting
from tests.isolation_util import REPO, run_isolated


class StillClock:
    """Time that moves only when the wait sleeps."""

    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now

    async def sleep(self, seconds: float) -> None:
        self.now += seconds


def _wait(clock, predicate, probe, **kw):
    kw.setdefault("what", "the thing waited for")
    return asyncio.run(
        waiting.wait_progress(
            predicate, probe, poll=1.0, clock=clock, sleep=clock.sleep, **kw
        )
    )


def returns_a_true_predicates_value_at_once(clock):
    assert _wait(clock, lambda: [7], lambda: 0) == [7]
    assert clock.now == 0.0


def extends_on_progress(clock):
    # a new probe value every 20 s until 70 s, true at 80 s: past the
    # first window of 30 s, never 30 s without progress
    got = _wait(
        clock,
        lambda: clock.now >= 80,
        lambda: min(clock.now // 20, 3),
        first_window=30.0,
        window=30.0,
    )
    assert got is True and clock.now == 80.0


def early_progress_never_shrinks_the_first_window(clock):
    # the probe moves once, at 1 s; the rule "window from the last
    # progress" alone would end the wait at 11 s (both deleted copies did)
    _wait(
        clock,
        lambda: clock.now >= 50,
        lambda: clock.now >= 1,
        first_window=60.0,
        window=10.0,
    )
    assert clock.now == 50.0


def raises_on_a_still_probe_after_its_window(clock):
    with pytest.raises(TimeoutError) as e:
        _wait(clock, lambda: False, lambda: {"exits": 0}, first_window=30.0)
    assert clock.now == 30.0
    said = str(e.value)
    assert "the thing waited for" in said and "its window" in said
    assert "{'exits': 0}" in said and "30.0 s" in said


def raises_on_its_ceiling_while_the_probe_keeps_changing(clock):
    # the case that hung the run: a live simnet broadcasts for ever, and
    # the predicate never comes true
    with pytest.raises(TimeoutError) as e:
        _wait(clock, lambda: False, lambda: clock.now, window=30.0)
    assert clock.now == waiting.WAIT_CEILING
    said = str(e.value)
    assert "the thing waited for" in said
    assert f"the ceiling of {waiting.WAIT_CEILING:g} s" in said
    assert "0.0 s after the probe last changed" in said


def a_probe_left_out_is_a_plain_bounded_wait(clock):
    with pytest.raises(TimeoutError):
        asyncio.run(
            waiting.wait_progress(
                lambda: False, what="x", first_window=5.0, poll=1.0,
                clock=clock, sleep=clock.sleep,
            )
        )
    assert clock.now == 5.0


def wait_for_broadcasts_names_the_recorders_still_short(clock):
    beacon = SimpleNamespace(
        **{name: [0] * 4 for name in waiting.ALL_DUTY_RECORDERS}
    )
    beacon.registrations, beacon.exits = [], [0]
    with pytest.raises(TimeoutError) as e:
        asyncio.run(
            waiting.wait_for_broadcasts(
                beacon, want=4, poll=1.0, clock=clock, sleep=clock.sleep
            )
        )
    assert "{'registrations': 0, 'exits': 1}" in str(e.value)
    beacon.registrations, beacon.exits = [0] * 4, [0] * 4
    asyncio.run(waiting.wait_for_broadcasts(beacon, want=4))


@pytest.mark.parametrize(
    "case",
    [
        returns_a_true_predicates_value_at_once,
        extends_on_progress,
        early_progress_never_shrinks_the_first_window,
        raises_on_a_still_probe_after_its_window,
        raises_on_its_ceiling_while_the_probe_keeps_changing,
        a_probe_left_out_is_a_plain_bounded_wait,
        wait_for_broadcasts_names_the_recorders_still_short,
    ],
    ids=lambda case: case.__name__,
)
def test_the_one_wait(case):
    case(StillClock())


def test_no_wait_outlasts_a_tests_limit_and_no_limit_the_drivers():
    import conftest  # the plugin object itself, not a second import

    from tests import isolation_util

    # three waits in a row is the most a test makes
    assert 3 * waiting.WAIT_CEILING < conftest.TEST_LIMIT
    assert isolation_util.DEFAULT_TIMEOUT < isolation_util.REAL_PROGRAM_LIMIT
    assert isolation_util.REAL_PROGRAM_LIMIT + conftest.HARD_GRACE < 1470


# -- the limit a test has: a pytest of its own, under tests/conftest.py ------

SLEEPER = """
import time

import pytest


@pytest.mark.limit(1)
def test_sleeper():
    time.sleep(60)


def test_neighbour():
    pass
"""

# SIGALRM blocked: what a main thread held in C code looks like to the
# handler. Only faulthandler's watchdog thread can end it, and the worker.
STUCK = """
import signal
import time

import conftest
import pytest

conftest.HARD_GRACE = 1.0


@pytest.mark.limit(1)
def test_stuck():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(60)


def test_neighbour():
    pass
"""


def _child_pytest(tmp_path, source: str, *args: str):
    test_file = tmp_path / "test_child.py"
    test_file.write_text(textwrap.dedent(source))
    return subprocess.run(
        [
            sys.executable, "-m", "pytest", str(test_file), "-q",
            "-c", f"{REPO}/pytest.ini", "--rootdir", str(tmp_path),
            # tests/conftest.py as a plugin: the child's file is not under tests/
            "-p", "conftest", "-p", "no:cacheprovider", *args,
        ],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
        env={**os.environ, "PYTHONPATH": f"{REPO}:{REPO}/tests"},
    )


def a_test_past_its_limit_fails_with_every_threads_stack(tmp_path):
    proc = _child_pytest(tmp_path, SLEEPER)
    out = proc.stdout + proc.stderr
    assert "1 failed, 1 passed" in out, out[-3000:]
    assert "LimitExceeded: outlived its limit of 1 s" in out
    assert "test_child.py::test_sleeper outlived its limit of 1 s" in out
    assert "--- thread MainThread:" in out and "time.sleep(60)" in out


def a_main_thread_the_signal_cannot_reach_costs_its_worker_one_test(tmp_path):
    proc = _child_pytest(tmp_path, STUCK, "-p", "xdist", "-n", "1")
    out = proc.stdout + proc.stderr
    assert "1 failed, 1 passed" in out, out[-3000:]
    assert "crashed while running" in out and "test_stuck" in out


@pytest.mark.parametrize(
    "case",
    [
        a_test_past_its_limit_fails_with_every_threads_stack,
        a_main_thread_the_signal_cannot_reach_costs_its_worker_one_test,
    ],
    ids=lambda case: case.__name__,
)
def test_a_test_that_hangs_costs_one_test(case, tmp_path):
    case(tmp_path)


# -- run_isolated ------------------------------------------------------------


def returns_the_childs_stdout():
    assert run_isolated("print('DONE 7')", "DONE") == "DONE 7\n"


def kills_a_child_that_outlives_its_timeout_and_shows_its_stderr():
    script = (
        "import sys, time\n"
        "print('before the hang', file=sys.stderr, flush=True)\n"
        "time.sleep(60)\n"
    )
    with pytest.raises(AssertionError) as e:
        run_isolated(script, "DONE", timeout=1)
    assert "killed after its 1 s" in str(e.value)
    assert "before the hang" in str(e.value)


def shows_the_stderr_of_a_child_that_fails():
    with pytest.raises(AssertionError, match="isolated test failed rc=3") as e:
        run_isolated("import sys; sys.stderr.write('why'); sys.exit(3)", "DONE")
    assert "why" in str(e.value)


@pytest.mark.parametrize(
    "case",
    [
        returns_the_childs_stdout,
        kills_a_child_that_outlives_its_timeout_and_shows_its_stderr,
        shows_the_stderr_of_a_child_that_fails,
    ],
    ids=lambda case: case.__name__,
)
def test_run_isolated(case):
    case()
