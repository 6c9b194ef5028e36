"""Clock-skew regression battery (ISSUE 10 satellite): drive
testutil/chaos.SkewedClock through every path the monotonic-clock
audit fixed or pinned.

The bug class (PR 8's `_arm`): duty deadlines live on the WALL
timeline (slots are genesis arithmetic) but retry/cooldown loops run
on real sleeps — comparing wall clocks across iterations means a host
clock step (NTP correction, VM migration, operator fat-finger)
silently aborts the remaining retries (forward step) or retries far
past expiry (backward step). The fix everywhere is the same: anchor
the wall deadline to `time.monotonic()` ONCE, loop on monotonic.

Audit coverage map (the five files ISSUE 10 names):
  core/parsigex.py  `_resend`  — fixed here, tested below
  core/bcast.py     `_submit`  — fixed here, tested below
  app/retry.py      `Retryer`  — fixed here, tested below
  core/cryptosvc.py breaker cooldown — already monotonic (PR 8);
                    pinned below under a live wall step
  p2p/transport.py  peer quarantine mute — already monotonic (PR 8);
                    pinned below under a live wall step
  core/consensus_qbft.py — durations already `time.monotonic`; only
                    the debug-sniffer wall timestamp remained, which
                    is a logging edge and carries the audited pragma
  core/cryptoplane.py `_arm` — the original regression test lives in
                    tests/test_hostplane.py (PR 8)
"""

from __future__ import annotations

import asyncio
import time

import pytest

from charon_tpu.core.deadline import SlotClock
from charon_tpu.testutil.chaos import SkewedClock
from charon_tpu.testutil.waiting import wait_until

# -- app/retry.Retryer -------------------------------------------------------


def test_retryer_survives_forward_wall_step_mid_retry():
    """A +1h wall step between attempts must NOT abort the remaining
    retry window (the old `now() + backoff >= deadline` compare did)."""
    from charon_tpu.app.retry import Retryer

    async def run():
        with SkewedClock() as clock:
            deadline = time.time() + 5.0
            calls = []

            async def flaky(duty):
                calls.append(1)
                if len(calls) == 1:
                    clock.step(3600.0)  # host clock jumps forward
                if len(calls) < 3:
                    raise ConnectionError("flaky bn")

            r = Retryer(deadline_of=lambda d: deadline, backoff=0.02)
            await r.retry("step", "duty", flaky)
            assert len(calls) == 3  # retried THROUGH the step

    asyncio.run(run())


def test_retryer_stops_at_deadline_despite_backward_wall_step():
    """A -1h step must not extend retries past the monotonic-anchored
    duty window (the old compare would have retried for an hour)."""
    from charon_tpu.app.retry import Retryer

    async def run():
        with SkewedClock() as clock:
            deadline = time.time() + 0.3
            calls = []

            async def always_down(duty):
                calls.append(1)
                if len(calls) == 1:
                    clock.step(-3600.0)
                raise ConnectionError("down")

            r = Retryer(deadline_of=lambda d: deadline, backoff=0.05)
            t0 = time.monotonic()
            await r.retry("step", "duty", always_down)
            assert time.monotonic() - t0 < 2.0  # bounded by the anchor
            assert len(calls) >= 2  # the step did not stop it either

    asyncio.run(run())


# -- core/bcast.Broadcaster._submit ------------------------------------------


def test_bcast_retry_survives_forward_wall_step():
    from charon_tpu.core.bcast import Broadcaster

    async def run():
        with SkewedClock() as clock:
            slot_clock = SlotClock(
                genesis_time=time.time(), slot_duration=1.0
            )  # duty deadline = slot_start + 30s window
            b = Broadcaster(beacon=None, clock=slot_clock)
            calls = []

            async def submit_fn():
                calls.append(1)
                if len(calls) == 1:
                    clock.step(3600.0)
                if len(calls) < 3:
                    raise ConnectionError("bn flap")
                return "accepted"

            from charon_tpu.core.types import Duty, DutyType

            duty = Duty(0, DutyType.ATTESTER)
            out = await b._submit(duty, submit_fn)
            assert out == "accepted"
            assert b.retried_total == 2  # both retries ran post-step

    asyncio.run(run())


def test_bcast_retry_still_bounded_by_duty_deadline():
    """Sanity: with the wall clock HONEST and the deadline already
    past, the first transient failure surfaces immediately."""
    from charon_tpu.core.bcast import Broadcaster
    from charon_tpu.core.types import Duty, DutyType

    async def run():
        slot_clock = SlotClock(
            genesis_time=time.time() - 1000.0, slot_duration=1.0
        )
        b = Broadcaster(beacon=None, clock=slot_clock)

        async def submit_fn():
            raise ConnectionError("bn flap")

        with pytest.raises(ConnectionError):
            await b._submit(Duty(0, DutyType.ATTESTER), submit_fn)

    asyncio.run(run())


# -- core/parsigex.ParSigEx._resend ------------------------------------------


class _FlakyTransport:
    """MemTransport duck type: fails the first `fail` sends."""

    def __init__(self, fail: int) -> None:
        self.fail = fail
        self.sends = 0
        self.nodes = []

    def attach(self, node) -> None:
        self.nodes.append(node)

    async def send(self, from_idx, duty, signed_set, tctx=None) -> None:
        self.sends += 1
        if self.sends <= self.fail:
            raise ConnectionError("link flap")


def test_parsigex_resend_survives_forward_wall_step():
    from charon_tpu.core.parsigex import ParSigEx
    from charon_tpu.core.types import Duty, DutyType

    async def run():
        with SkewedClock() as clock:
            slot_clock = SlotClock(
                genesis_time=time.time(), slot_duration=1.0
            )
            transport = _FlakyTransport(fail=2)
            ex = ParSigEx(
                share_idx=0, transport=transport, clock=slot_clock
            )
            duty = Duty(0, DutyType.ATTESTER)
            await ex.broadcast(duty, {})  # inline attempt fails -> task
            clock.step(3600.0)  # step while the retry task backs off
            await wait_until(
                lambda: ex.resend_total, "the resend through the clock step"
            )
            assert ex.resend_total == 1  # resent THROUGH the step
            assert transport.sends == 3  # inline + failed retry + ok

    asyncio.run(run())


# -- core/cryptosvc.CircuitBreaker cooldown ----------------------------------


def test_breaker_cooldown_immune_to_wall_step():
    """The forged-flood breaker's open->half_open cooldown runs on
    monotonic: a +1h wall step must NOT open the quarantine gate early
    (a forged-flooding tenant could otherwise skew its own clock's
    host... the breaker simply never consults wall time)."""
    from charon_tpu.core.cryptosvc import CircuitBreaker, TenantQuota

    quota = TenantQuota(
        breaker_window=16,
        breaker_min_lanes=4,
        breaker_threshold=0.5,
        breaker_cooldown=0.4,
    )
    with SkewedClock() as clock:
        br = CircuitBreaker(quota)
        br.record(ok=0, failed=8)  # forged flood trips it
        assert br.state == "open" and br.quarantined()
        clock.step(3600.0)
        assert br.quarantined() and br.state == "open", (
            "wall step must not fast-forward the cooldown"
        )
        time.sleep(0.45)  # real (monotonic) cooldown elapses
        assert br.quarantined() and br.state == "half_open"
        br.record(ok=4, failed=0)  # clean probe closes it
        assert br.state == "closed" and not br.quarantined()


# -- p2p quarantine mute -----------------------------------------------------


def test_peer_quarantine_mute_immune_to_wall_step():
    """The transport's per-peer codec quarantine times mutes on
    monotonic: a wall step neither expires a mute early (forward) nor
    extends it (backward)."""
    from charon_tpu.p2p.quarantine import PeerQuarantine

    with SkewedClock() as clock:
        q = PeerQuarantine(strikes=3, window=10.0, base=0.4)
        for _ in range(3):
            q.strike(7)
        assert q.muted(7)
        clock.step(3600.0)
        assert q.muted(7), "wall step must not expire the mute"
        clock.step(-7200.0)
        assert q.muted(7)
        time.sleep(0.45)  # the real mute window
        assert not q.muted(7)


# -- tbls ladder demotion race (surfaced by this PR's executor fixes) --------


def test_resilient_ladder_demotes_exactly_once_under_thread_race():
    """ResilientImpl is hammered from executor threads (decode pool +
    the overload-shed run_in_executor hops): N threads racing failures
    on the active rung must demote it exactly ONCE — the unlocked
    bookkeeping used to double-demote past a healthy rung."""
    import threading

    from charon_tpu.tbls.resilient import ResilientImpl

    class Boom:
        def verify_batch(self, items):
            raise RuntimeError("wedged backend")

    class Ok:
        def verify_batch(self, items):
            return [True]

    ladder = ResilientImpl([Boom(), Ok()], demote_after=2)
    barrier = threading.Barrier(8)
    results = []

    def worker():
        barrier.wait()
        results.append(ladder.verify_batch([b"x"]))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [[True]] * 8
    assert ladder.demotions == [0], "demotion must be recorded once"
    assert ladder.active == 1
