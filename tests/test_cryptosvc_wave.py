"""Wave hints through the multi-tenant service (ISSUE 27): a submission's
`wave=((key, expected), ...)` reaches the shared coalescer with its keys
namespaced by tenant, so several clusters on one coalescer close a window
when ALL their open waves are whole and one tenant's jobs never make
another tenant's wave whole. The coalescer's clock stands still and its
timer is a year long (tests/test_cryptoplane.py): a window closes on its
timer only when a test says so."""

from __future__ import annotations

import asyncio

import pytest

from charon_tpu.core.cryptosvc import CryptoPlaneService, TenantQuota
from tests.test_cryptoplane import (  # noqa: F401 — `clock` is a fixture
    _all, _coalescer, _lane, _ring_timer, _settle, clock,
)


def _two_tenants(coal, **kw):
    svc = CryptoPlaneService(coal, round_interval=0.001, **kw)
    return svc, svc.register("cluster-a"), svc.register("cluster-b")


def test_keys_of_two_tenants_do_not_complete_each_other(clock):
    """Both clusters call their wave "5/attester" and expect two sets.
    One set from each is two jobs under that name and no whole wave."""
    coal, fake, stats = _coalescer()
    svc, a, b = _two_tenants(coal)
    wave = (("5/attester", 2),)

    async def main():
        first = [asyncio.create_task(p.verify([_lane()], wave=wave)) for p in (a, b)]
        await _settle(20)
        assert len(coal._verify_q) == 2 and fake.verify_calls == 0
        assert set(coal._waves) == {
            ("verify", ("cluster-a", "5/attester")),
            ("verify", ("cluster-b", "5/attester")),
        }
        # cluster-a's wave comes whole: cluster-b's is still open, so
        # the shared window stays open for it
        second_a = asyncio.create_task(a.verify([_lane()], wave=wave))
        await _settle(20)
        assert len(coal._verify_q) == 3 and fake.verify_calls == 0
        second_b = asyncio.create_task(b.verify([_lane()], wave=wave))
        return await _all(*first, second_a, second_b)

    try:
        assert asyncio.run(main()) == [[True]] * 4
    finally:
        svc.close()
        coal.close()
    assert fake.verify_calls == 1 and fake.verify_lane_count == 4
    (s,) = stats
    assert s.window_closed_by == "complete" and s.jobs == 4
    assert s.tenant_lanes == (("cluster-a", 2), ("cluster-b", 2))


def test_a_tenant_without_hints_keeps_the_shared_window_on_its_timer(clock):
    """One tenant cannot close another's window early, and a tenant that
    sends no hint (an older node, the remote client's local rung) leaves
    the window exactly as it was before hints."""
    coal, fake, stats = _coalescer()
    svc, a, b = _two_tenants(coal)

    async def main():
        jobs = [asyncio.create_task(a.verify([_lane()], wave=(("w", 1),))),
                asyncio.create_task(b.verify([_lane()]))]
        await _settle(20)
        assert len(coal._verify_q) == 2 and fake.verify_calls == 0
        _ring_timer(coal, clock)
        return await _all(*jobs)

    try:
        assert asyncio.run(main()) == [[True]] * 2
    finally:
        svc.close()
        coal.close()
    assert [s.window_closed_by for s in stats] == ["timer"]


class _Recorder:
    """A coalescer that only records what the service hands it."""

    t = 2

    def __init__(self, wave_hints):
        if wave_hints:
            self.wave_hints = True
        self.kwargs: list[dict] = []

    async def verify(self, items, **kwargs):
        self.kwargs.append(kwargs)
        return [True] * len(items)

    async def recombine(self, pubshares, roots, partials, group_pks, indices, **kwargs):
        self.kwargs.append(kwargs)
        return [b"\x01" * 96] * len(roots), [True] * len(roots)


@pytest.mark.parametrize("kind", ["verify", "recombine"])
def test_the_service_namespaces_the_hint_and_keeps_it_from_the_quarantine(kind):
    """Shared coalescer: the key arrives as (tenant, key). Quarantined
    (the tenant's own short-window coalescer) or a coalescer that takes
    no hints: no `wave` at all, so those windows close as they did."""
    shared, deaf, own = _Recorder(True), _Recorder(False), _Recorder(True)

    async def submit(plane):
        wave = (("5/attester", 4), ("5/sync", 4))
        if kind == "verify":
            return await plane.verify([1], deadline=9.0, wave=wave)
        return await plane.recombine([[1]], [b"r"], [[1]], [1], [[1]], deadline=9.0, wave=wave)

    async def main():
        svc = CryptoPlaneService(shared, round_interval=0.001,
                                 quarantine_factory=lambda tenant: own)
        plane = svc.register("cluster-a", TenantQuota())
        await submit(plane)
        await submit(CryptoPlaneService(deaf, round_interval=0.001).register("cluster-a"))
        svc.tenant("cluster-a").breaker._transition("open")
        await submit(plane)
        svc.close()

    asyncio.run(main())
    assert shared.kwargs == [{
        "deadline": 9.0, "tenant": "cluster-a",
        "wave": ((("cluster-a", "5/attester"), 4), (("cluster-a", "5/sync"), 4)),
    }]
    assert deaf.kwargs == [{"deadline": 9.0, "tenant": "cluster-a"}]
    assert own.kwargs == [{"deadline": 9.0, "tenant": "cluster-a"}]
