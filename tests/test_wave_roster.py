"""Await the operators that sent last slot, not the static n (ISSUE 33).

The roster (core/parsigex.WaveRoster): who sent a partial-signature set for
the newest earlier slot of a duty type. The hint (core/parsigex.WaveSet): a
set names its sender, the senders its wave awaits and n. The close rule
(core/cryptoplane "What closes a window"): a wave is whole when a job of
every awaited sender is in the window. The coalescer's clock stands still
and its timer is a year long (tests/test_cryptoplane.py): a window closes
on its timer only when a test says so."""

from __future__ import annotations

import asyncio

import pytest

from charon_tpu.app import tracer
from charon_tpu.core import eth2data as d
from charon_tpu.core.cryptosvc import CryptoPlaneService
from charon_tpu.core.parsigex import Eth2Verifier, WaveRoster, WaveSet
from charon_tpu.core.types import Duty, DutyType, PubKey
from charon_tpu.core.validatorapi import ValidatorAPI
from charon_tpu.crypto import g1g2
from tests.test_cryptoplane import (  # noqa: F401 — `clock` is a fixture
    FORK, YEAR, _all, _coalescer, _lane, _ring_timer, _settle, clock,
)

ALL7 = frozenset(range(1, 8))
UP = frozenset({1, 3, 4, 6, 7})  # dv-5of7-1k.node-down: operators 2 and 5 silent


def att(slot):
    return Duty(slot, DutyType.ATTESTER)


def wave_of(roster, duty, senders):
    return [roster.hint(duty, s) for s in senders]


# -- the roster -----------------------------------------------------------------


def test_before_any_wave_of_a_type_the_roster_awaits_all_n():
    roster = WaveRoster(range(1, 8))
    assert roster.hint(att(5), 3) == WaveSet(sender=3, awaited=ALL7, n=7)
    # the rest of that first wave too: nothing earlier has been seen
    assert roster.hint(att(5), 1).awaited == ALL7


def test_the_roster_learns_from_the_newest_earlier_slot_only():
    roster = WaveRoster(range(1, 8))
    wave_of(roster, att(5), UP)
    wave_of(roster, att(6), {1, 3})
    # slot 7 awaits who sent for slot 6, not who ever sent
    assert [h.awaited for h in wave_of(roster, att(7), (1, 3))] == [frozenset({1, 3})] * 2
    # a slot with no duty of the type leaves no trace: slot 9 looks back to 7
    assert roster.hint(att(9), 1).awaited == frozenset({1, 3})
    assert roster.hint(att(9), 1).n == 7


def test_the_roster_is_kept_per_duty_type():
    roster = WaveRoster(range(1, 5))
    wave_of(roster, att(5), (1, 2, 4))
    # the first aggregator wave knows nothing of who attested
    agg = Duty(5, DutyType.AGGREGATOR)
    assert roster.hint(agg, 1).awaited == frozenset({1, 2, 3, 4})
    assert roster.hint(att(6), 1).awaited == frozenset({1, 2, 4})
    assert roster.hint(Duty(6, DutyType.AGGREGATOR), 2).awaited == frozenset({1, 2})


def test_a_sender_that_was_not_awaited_is_always_among_its_own_awaited():
    roster = WaveRoster(range(1, 5))
    wave_of(roster, att(5), (1, 2))
    hint = roster.hint(att(6), 4)
    assert hint.sender == 4 and hint.awaited == frozenset({1, 2, 4})
    # the others of that wave do not await it: its hint alone does
    assert roster.hint(att(6), 1).awaited == frozenset({1, 2})


def test_a_sender_that_returns_is_awaited_from_the_wave_after():
    roster = WaveRoster(range(1, 5))
    wave_of(roster, att(5), (1, 2, 3, 4))
    assert roster.hint(att(6), 1).awaited == frozenset({1, 2, 3, 4})  # 3 falls silent in 6
    wave_of(roster, att(6), (2, 4))
    assert roster.hint(att(7), 1).awaited == frozenset({1, 2, 4})
    wave_of(roster, att(7), (2, 4, 3))  # and is back in 7, not awaited by the others
    assert [h.awaited for h in wave_of(roster, att(8), (1, 3))] == [frozenset({1, 2, 3, 4})] * 2


def test_a_set_of_an_older_slot_awaits_everyone_and_teaches_nothing():
    roster = WaveRoster(range(1, 5))
    wave_of(roster, att(5), (1, 2))
    wave_of(roster, att(6), (1, 2))
    late = roster.hint(att(5), 3)  # slot 5's straggler, a slot late
    assert late == WaveSet(3, frozenset({1, 2, 3, 4}), 4)
    assert roster.hint(att(6), 1).awaited == frozenset({1, 2})
    assert roster.hint(att(7), 1).awaited == frozenset({1, 2})


def test_the_roster_reads_the_live_registry_when_an_operator_joins():
    registry = {i: {} for i in range(1, 5)}
    roster = WaveRoster(registry)
    assert roster.hint(att(5), 1).n == 4
    registry[5] = {}  # Node.apply_reshare grows the shared dict in place
    first = roster.hint(Duty(5, DutyType.RANDAO), 1)
    assert first.n == 5 and first.awaited == frozenset(range(1, 6))


# -- the submitters -------------------------------------------------------------


def _cluster(coal, n=4):
    """One node's submitters of sets over one roster and one tenant's
    handle, and what makes a set of operator `idx` for a slot."""
    svc = CryptoPlaneService(coal, round_interval=0.001)
    plane = svc.register("cluster-a")
    pk = PubKey("0x" + "ab" * 48)
    share, sig = g1g2.g1_to_bytes(g1g2.G1_GEN), g1g2.g2_to_bytes(g1g2.G2_GEN)
    pubshares_by_idx = {i: {pk: share} for i in range(1, n + 1)}
    roster = WaveRoster(pubshares_by_idx)
    vapi = ValidatorAPI(1, pubshares_by_idx[1], FORK, plane=plane, roster=roster)
    verifier = Eth2Verifier(FORK, pubshares_by_idx, plane=plane, roster=roster)

    def peer(slot, idx, signature=sig):
        duty = Duty(slot, DutyType.RANDAO)
        signed = {pk: d.ParSignedData(data=d.SignedData("randao", slot // 32, signature),
                                      share_idx=idx)}
        return asyncio.create_task(verifier.verify_async(duty, signed))

    def mine(slot):
        return asyncio.create_task(vapi.submit_randao(slot, pk, sig))

    return svc, roster, peer, mine


def test_the_nodes_own_submission_counts_under_its_own_index(clock):
    coal, _fake, stats = _coalescer()
    svc, roster, peer, mine = _cluster(coal)

    async def main():
        jobs = [mine(64), peer(64, 2)]
        await _settle(20)
        _ring_timer(coal, clock)
        await _all(*jobs)

    try:
        asyncio.run(main())
    finally:
        svc.close()
        coal.close()
    assert roster.hint(Duty(96, DutyType.RANDAO), 2).awaited == frozenset({1, 2})
    (s,) = stats
    assert (s.sets_expected, s.sets_seen, s.sets_awaited) == (4, 2, 4)


def test_a_set_counts_whether_or_not_it_verifies(clock):
    """The forged set of a slot is still a set from its sender: a forger
    is awaited next slot like anyone who sent."""
    coal, fake, _stats = _coalescer()
    fake.verify_host = lambda pks, msgs, sigs, rng=None: [False] * len(pks)
    svc, roster, peer, _mine = _cluster(coal)

    async def main():
        job = peer(64, 3)
        await _settle(20)
        _ring_timer(coal, clock)
        return await asyncio.wait_for(job, 30)

    try:
        assert asyncio.run(main()) is False
    finally:
        svc.close()
        coal.close()
    assert roster.hint(Duty(96, DutyType.RANDAO), 1).awaited == frozenset({1, 3})


def test_an_outage_and_the_return_through_the_nodes_own_submitters(clock):
    """Operator 3 of a 4-operator cluster misses two slots and comes back.
    First slot out: still awaited, the window falls to its timer. Second:
    not awaited, the window closes whole on three sets. Its first set
    back rides along in the same flush; from the slot after it is awaited."""
    coal, fake, stats = _coalescer()
    svc, _roster, peer, mine = _cluster(coal)

    async def wave(slot, peers, whole):
        jobs = [mine(slot), *(peer(slot, i) for i in peers)]
        if not whole:
            await _settle(20)
            assert fake.verify_calls == len(stats) and not coal._flush_task.done()
            _ring_timer(coal, clock)
        await _all(*jobs)

    async def main():
        await wave(32, (2, 3, 4), whole=True)
        await wave(64, (2, 4), whole=False)  # the first slot of the outage
        await wave(96, (2, 4), whole=True)
        await wave(128, (2, 4, 3), whole=True)  # the first slot after it
        await wave(160, (2, 4), whole=False)  # 3 is awaited again, and missed

    try:
        asyncio.run(main())
    finally:
        svc.close()
        coal.close()
    assert [(s.window_closed_by, s.sets_expected, s.sets_seen, s.sets_awaited, s.window_closed_short)
            for s in stats] == [
        ("complete", 4, 4, 4, False), ("timer", 4, 3, 4, False), ("complete", 4, 3, 3, True),
        ("complete", 4, 4, 4, False), ("timer", 4, 3, 4, False)]
    assert fake.verify_calls == 5, "one program a wave: no set flushed alone"
    assert coal.windows_closed == {"complete": 3, "timer": 2} and coal.windows_closed_short == 1


# -- the close rule ---------------------------------------------------------------


def _sets(coal, key, senders, awaited, n=7):
    return [asyncio.create_task(coal.verify([_lane()], wave=((key, WaveSet(s, frozenset(awaited), n)),)))
            for s in senders]


def test_a_wave_is_whole_on_the_awaited_senders_with_two_operators_silent(clock):
    coal, fake, stats = _coalescer()

    async def main():
        first = _sets(coal, "duty-6", (1, 3, 4, 6), UP)
        await _settle()
        assert len(coal._verify_q) == 4 and fake.verify_calls == 0, "7 is awaited"
        return await _all(*first, *_sets(coal, "duty-6", (7,), UP))

    assert asyncio.run(main()) == [[True]] * 5
    assert fake.verify_calls == 1 and fake.verify_lane_count == 5
    (s,) = stats
    assert s.window_closed_by == "complete" and s.jobs == 5
    assert s.window_span == (1000.0, 1000.0), "nothing was waited out"
    assert coal.windows_closed == {"complete": 1}


def test_a_sender_that_was_not_awaited_rides_along_in_the_same_flush(clock):
    """Operator 2 is back: its set is in the window when the awaited ones
    come whole, so it leaves with them in ONE program."""
    coal, fake, stats = _coalescer()

    async def main():
        back = _sets(coal, "duty-9", (2,), UP | {2})
        rest = _sets(coal, "duty-9", (1, 3, 4, 6), UP)
        await _settle()
        assert fake.verify_calls == 0, "7 is awaited"
        return await _all(*back, *rest, *_sets(coal, "duty-9", (7,), UP))

    assert asyncio.run(main()) == [[True]] * 6
    assert fake.verify_calls == 1 and fake.verify_lane_count == 6
    (s,) = stats
    assert (s.window_closed_by, s.sets_expected, s.sets_seen, s.sets_awaited) == ("complete", 7, 6, 6)


def test_a_returning_set_that_trails_its_wave_flushes_in_the_next_window(clock):
    """Later than the wave's close it is alone in its window, as a set
    later than the timer always was: it still awaits the others, so the
    timer closes it, and it is verified like any other."""
    coal, fake, stats = _coalescer()

    async def main():
        await _all(*_sets(coal, "duty-9", UP, UP))
        late = _sets(coal, "duty-9", (2,), UP | {2})
        await _settle()
        assert fake.verify_calls == 1 and not coal._flush_task.done()
        _ring_timer(coal, clock)
        return await _all(*late)

    assert asyncio.run(main()) == [[True]]
    assert [(s.window_closed_by, s.sets_seen, s.sets_awaited) for s in stats] == [
        ("complete", 5, 5), ("timer", 1, 6)]
    assert coal.windows_closed_short == 1


def test_an_awaited_sender_missing_leaves_the_window_to_its_timer(clock):
    coal, fake, stats = _coalescer()

    async def main():
        jobs = _sets(coal, "duty-6", (1, 3, 4, 6), UP)
        await _settle()
        assert fake.verify_calls == 0 and not coal._flush_task.done()
        _ring_timer(coal, clock)
        return await _all(*jobs)

    assert asyncio.run(main()) == [[True]] * 4
    (s,) = stats
    assert (s.window_closed_by, s.sets_expected, s.sets_seen, s.sets_awaited) == ("timer", 7, 4, 5)
    assert s.window_closed_short is False, "it did not close whole"
    assert coal.current_window == pytest.approx(YEAR * coal.WINDOW_GROW), "a timer close feeds the controller"
    assert coal.windows_closed == {"timer": 1} and coal.windows_closed_short == 0


def test_hints_of_one_wave_that_disagree_keep_the_union(clock):
    """Two submitters with different rosters, or a plain count beside
    named senders: the window closes on no less than any of them awaits."""
    coal, fake, stats = _coalescer()

    async def main():
        jobs = _sets(coal, "k", (1,), {1, 3}) + _sets(coal, "k", (3,), {1, 3, 4})
        await _settle()
        assert fake.verify_calls == 0, "the second hint awaits 4"
        jobs += _sets(coal, "k", (4,), {4})
        out = await _all(*jobs)
        # a count and names on one key: both must hold
        jobs = _sets(coal, "m", (1, 3), {1, 3}) + [
            asyncio.create_task(coal.verify([_lane()], wave=(("m", 4),)))]
        await _settle()
        assert fake.verify_calls == 1, "three jobs of the four counted"
        jobs += _sets(coal, "m", (6,), {6})
        return out + await _all(*jobs)

    assert asyncio.run(main()) == [[True]] * 7
    assert [(s.window_closed_by, s.sets_expected, s.sets_seen, s.sets_awaited) for s in stats] == [
        ("complete", 7, 3, 3), ("complete", 7, 4, 4)]


def test_named_senders_of_two_tenants_do_not_complete_each_other(clock):
    """Both clusters call their wave "5/attester" and await senders 1 and
    2: sender 1 of one and sender 2 of the other are no whole wave."""
    coal, fake, stats = _coalescer()
    svc = CryptoPlaneService(coal, round_interval=0.001)
    a, b = svc.register("cluster-a"), svc.register("cluster-b")

    def send(plane, sender):
        hint = WaveSet(sender, frozenset({1, 2}), 4)
        return asyncio.create_task(plane.verify([_lane()], wave=(("5/attester", hint),)))

    async def main():
        first = [send(a, 1), send(b, 2)]
        await _settle(20)
        assert len(coal._verify_q) == 2 and fake.verify_calls == 0
        second = send(a, 2)
        await _settle(20)
        assert len(coal._verify_q) == 3 and fake.verify_calls == 0, "cluster-b still awaits its 1"
        return await _all(*first, second, send(b, 1))

    try:
        assert asyncio.run(main()) == [[True]] * 4
    finally:
        svc.close()
        coal.close()
    (s,) = stats
    assert (s.window_closed_by, s.sets_expected, s.sets_seen, s.sets_awaited) == ("complete", 8, 4, 4)
    assert s.window_closed_short is True


def test_a_job_without_a_hint_still_holds_the_window_to_its_timer(clock):
    coal, fake, stats = _coalescer()

    async def main():
        jobs = _sets(coal, "duty-6", UP, UP) + [asyncio.create_task(coal.verify([_lane()]))]
        await _settle()
        assert fake.verify_calls == 0 and not coal._flush_task.done()
        _ring_timer(coal, clock)
        return await _all(*jobs)

    assert asyncio.run(main()) == [[True]] * 6
    (s,) = stats
    assert s.window_closed_by == "timer" and s.jobs == 6
    assert (s.sets_expected, s.sets_seen, s.sets_awaited) == (None, None, None)


# -- the flush and the span -----------------------------------------------------------


def test_the_flush_and_its_span_say_n_expected_and_fewer_awaited(clock):
    coal, _fake, stats = _coalescer()
    t = tracer.Tracer()
    bridge = tracer.plane_span_bridge(t)

    async def main():
        with tracer.span("parsigex.verify", tracer=t):
            await _all(*_sets(coal, "duty-6", UP, UP))

    asyncio.run(main())
    (s,) = stats
    assert (s.sets_expected, s.sets_seen, s.sets_awaited) == (7, 5, 5)
    assert s.window_closed_by == "complete" and s.window_closed_short is True
    assert coal.windows_closed == {"complete": 1} and coal.windows_closed_short == 1
    bridge(s)
    (window,) = [sp.attrs for sp in t.spans if sp.name == "cryptoplane.window"]
    assert (window["sets_expected"], window["sets_seen"], window["sets_awaited"]) == (7, 5, 5)
    assert window["closed_by"] == "complete" and window["verify_jobs"] == 5


def test_a_whole_cluster_awaits_n_and_counts_no_short_close(clock):
    """The healthy cells: every operator sent last slot, so every window
    awaits n, closes `complete` on n and the short-close count stays 0."""
    coal, _fake, stats = _coalescer()
    roster = WaveRoster(range(1, 8))

    async def main():
        for slot in (5, 6, 7):
            hints = wave_of(roster, att(slot), ALL7)
            await _all(*(coal.verify([_lane()], wave=((slot, h),)) for h in hints))

    asyncio.run(main())
    assert [(s.window_closed_by, s.sets_expected, s.sets_seen, s.sets_awaited, s.window_closed_short)
            for s in stats] == [("complete", 7, 7, 7, False)] * 3
    assert coal.windows_closed_short == 0 and coal.current_window == YEAR


def test_the_short_close_family_is_exported_beside_the_closes_by_cause():
    from charon_tpu.app.metrics import ClusterMetrics

    m = ClusterMetrics("0xhash", "c", "node0")
    m.labels(m.plane_windows_closed, "complete").inc(3)
    m.labels(m.plane_windows_closed_short).inc(2)
    lines = m.render().decode().splitlines()
    (short,) = [ln for ln in lines if ln.startswith("tpu_plane_windows_closed_short_total{")]
    (whole,) = [ln for ln in lines if ln.startswith("tpu_plane_windows_closed_total{")]
    assert short.endswith(" 2.0") and 'cause="complete"' in whole and whole.endswith(" 3.0")
