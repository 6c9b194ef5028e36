"""The one bounded way a simnet / e2e test waits.

A loaded CI box can starve an event loop for long stretches, so a wait
asks for fresh progress per window rather than raw speed across one
fixed bound (the pattern proven by
tests/test_simnet.py::test_simnet_survives_fuzzed_beacon). But a live
simnet broadcasts every slot for ever, so progress alone never ends a
wait whose predicate cannot come true: every wait also has an absolute
ceiling counted from the call. A wait may be slow; it may not be
endless.
"""

from __future__ import annotations

import asyncio
import time

# No wait outlives this many seconds from its call, whatever its probe
# does. Sized from the take-up run of ISSUE 41 (six xdist workers, load
# average 17 on 8 cores): the longest wait of a passing simnet test was
# under 30 s, which fits three times; and the most waits one test makes
# in a row (three: test_chaos_scenarios, test_cryptosvc_chaos,
# test_reshare_scenarios) stay under the per-test limit of
# tests/conftest.py (TEST_LIMIT 300 s).
WAIT_CEILING = 90.0

# every recorder list on BeaconMock that a full-duty e2e run fills
ALL_DUTY_RECORDERS = (
    "attestations",
    "proposals",
    "aggregates",
    "sync_messages",
    "contributions",
    "registrations",
    "exits",
)


async def wait_progress(
    predicate,
    probe=lambda: None,
    *,
    what: str,
    first_window: float = 60.0,
    window: float = 30.0,
    poll: float = 0.05,
    clock=time.monotonic,
    sleep=asyncio.sleep,
):
    """Await `predicate()` truthy and return its value.

    The deadline starts `first_window` out and moves to `window` from
    now whenever `probe()` reads a new value (never inwards: early
    progress must not shrink what remains), but never past WAIT_CEILING
    from the call. A probe left out never changes: the wait is then a
    plain bounded one of `first_window` seconds.

    On either end the TimeoutError says what was waited for, which end
    it was, the probe's last value and the seconds."""
    start = clock()
    ceiling = start + WAIT_CEILING
    deadline = min(start + first_window, ceiling)
    last = probe()
    progressed_at = start  # when the probe last read a new value
    while True:
        value = predicate()
        if value:
            return value
        now = clock()
        snapshot = probe()
        if snapshot != last:
            last = snapshot
            progressed_at = now
            deadline = min(max(deadline, now + window), ceiling)
        if now >= deadline:
            end = (
                f"the ceiling of {WAIT_CEILING:g} s"
                if deadline >= ceiling
                else "its window"
            )
            raise TimeoutError(
                f"waited {now - start:.1f} s for {what}: reached {end}, "
                f"{now - progressed_at:.1f} s after the probe last "
                f"changed; probe last read {last!r}"
            )
        await sleep(poll)


async def wait_until(predicate, what: str, within: float = 10.0):
    """The plain bounded wait for what takes an event-loop turn or a
    round trip on localhost: `predicate()` truthy within `within`
    seconds, polled every 10 ms."""
    return await wait_progress(
        predicate, what=what, first_window=within, poll=0.01
    )


async def wait_for_broadcasts(
    beacon, want: int = 4, recorders=ALL_DUTY_RECORDERS, **wait
) -> None:
    """Wait until every named BeaconMock recorder holds >= `want`
    entries; each fresh broadcast is progress. The error names the
    recorders still short. `wait` goes on to wait_progress (windows,
    a test's still clock)."""

    def short() -> dict[str, int]:
        return {
            name: len(getattr(beacon, name))
            for name in recorders
            if len(getattr(beacon, name)) < want
        }

    await wait_progress(
        lambda: not short(),
        probe=short,
        what=f"{want} broadcasts on each of {', '.join(recorders)} "
        "(probe: the recorders still short)",
        **wait,
    )
