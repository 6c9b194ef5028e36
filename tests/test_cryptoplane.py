"""SlotCoalescer: concurrent duties' crypto merges into ONE device call.

VERDICT r3 next-step 3 acceptance: two simultaneous duties produce one
batched device program. The device is a counting fake backed by the
pure-python oracle so this tier stays compile-free; the real sharded
plane (parallel/mesh.SlotCryptoPlane) runs the identical coalescer code
path in the slow tier (test_mesh.py::test_coalescer_on_real_mesh) and in
__graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import asyncio

import pytest

from charon_tpu import tbls
from charon_tpu.core import eth2data as d
from charon_tpu.core.cryptoplane import SlotCoalescer
from charon_tpu.core.parsigex import Eth2Verifier
from charon_tpu.core.sigagg import AggregationError, SigAgg
from charon_tpu.core.types import Duty, DutyType, pubkey_from_bytes
from charon_tpu.crypto import shamir
from charon_tpu.eth2util.signing import ForkInfo
from charon_tpu.tbls.python_impl import PythonImpl

FORK = ForkInfo(
    genesis_validators_root=b"\x11" * 32,
    fork_version=b"\x00\x00\x00\x01",
    genesis_fork_version=b"\x00" * 4,
)
T = 3


class FakePlane:
    """Counting stand-in for SlotCryptoPlane: same host-facing API
    (t, verify_host, recombine_host), pure-python recombination, no
    device. Lets the fast tier assert HOW MANY device programs the
    coalescer launches."""

    def __init__(self, t: int):
        self.t = t
        self.verify_calls = 0
        self.verify_lane_count = 0
        self.recombine_calls = 0
        self.recombine_lane_count = 0

    def verify_host(self, pks, msgs, sigs, rng=None):
        self.verify_calls += 1
        self.verify_lane_count += len(pks)
        return [True] * len(pks)

    def recombine_host(self, pubshares, msgs, partials, group_pks, indices, rng=None):
        self.recombine_calls += 1
        self.recombine_lane_count += len(msgs)
        sigs = [
            shamir.threshold_aggregate_g2(dict(zip(idx, parts)))
            for idx, parts in zip(indices, partials)
        ]
        return sigs, [True] * len(msgs)


def _att_data(slot: int) -> d.AttestationData:
    return d.AttestationData(
        slot=slot,
        index=0,
        beacon_block_root=b"\x22" * 32,
        source=d.Checkpoint(epoch=0, root=b"\x00" * 32),
        target=d.Checkpoint(epoch=1, root=b"\x33" * 32),
    )


def _duty_workload(impl: PythonImpl, slot: int):
    """One validator's attestation duty: (pubkey, psigs, root, expected
    group signature, pubshares_by_idx rows)."""
    secret = impl.generate_secret_key()
    shares = impl.threshold_split(secret, 4, T)
    group_pk = impl.secret_to_public_key(secret)
    pk = pubkey_from_bytes(group_pk)

    att = d.Attestation(aggregation_bits=(True,), data=_att_data(slot))
    unsigned = d.SignedData("attestation", att)
    root = unsigned.signing_root(FORK, slot // 32)
    psigs = [
        d.ParSignedData(
            data=unsigned.with_signature(impl.sign(shares[i], root)),
            share_idx=i,
        )
        for i in (1, 2, 3)
    ]
    expected = impl.threshold_aggregate(
        {i: p.data.signature for i, p in zip((1, 2, 3), psigs)}
    )
    pubshares = {
        i: impl.secret_to_public_key(shares[i]) for i in shares
    }
    return pk, group_pk, psigs, root, expected, pubshares


@pytest.mark.parametrize(
    "second,programs",
    [(Duty(6, DutyType.ATTESTER), 1), (Duty(5, DutyType.SYNC_MESSAGE), 2)],
    ids=["one-kind-one-program", "two-kinds-a-program-each"],
)
def test_two_duties_one_device_call(second, programs):
    """Two simultaneous duties' SigAgg recombinations coalesce into ONE
    plane program where they are one kind of duty (two slots' attester
    duties) and leave as a program each where they are two kinds (ISSUE
    38: a flush holds one kind, on its own bucket); either way each
    duty still gets its own correct group sig."""
    impl = PythonImpl()
    tbls.set_implementation(impl)
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=0.01)

    pk1, gpk1, psigs1, root1, want1, ps1 = _duty_workload(impl, slot=5)
    pk2, gpk2, psigs2, root2, want2, ps2 = _duty_workload(impl, slot=5)

    pubshares_by_idx = {
        i: {pk1: ps1[i], pk2: ps2[i]} for i in (1, 2, 3, 4)
    }
    agg = SigAgg(
        threshold=T,
        fork=FORK,
        plane=plane,
        pubshares_by_idx=pubshares_by_idx,
    )
    out: dict = {}

    async def on_agg(duty, data_set):
        out.update(data_set)

    agg.subscribe(on_agg)

    async def main():
        d1 = Duty(5, DutyType.ATTESTER)
        await asyncio.gather(
            agg.aggregate(d1, {pk1: psigs1}),
            agg.aggregate(second, {pk2: psigs2}),
        )

    asyncio.run(main())
    assert fake.recombine_calls == programs
    assert fake.recombine_lane_count == 2
    assert plane.coalesced_flushes == 2 - programs
    assert out[pk1].signature == want1
    assert out[pk2].signature == want2
    # the recovered signatures actually verify against the group keys
    impl.verify(gpk1, root1, out[pk1].signature)
    impl.verify(gpk2, root2, out[pk2].signature)


def test_verify_lanes_coalesce_across_components():
    """Concurrent verify submissions (the shape ParSigEx inbound sets and
    VC partial-sig checks produce) merge into one device program;
    malformed encodings fail on host without reaching the device."""
    impl = PythonImpl()
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=0.01)

    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    root = b"\x44" * 32
    sig = impl.sign(sk, root)

    async def main():
        r1, r2 = await asyncio.gather(
            plane.verify([(pk, root, sig), (pk, root, b"\x00" * 96)]),
            plane.verify([(pk, root, sig)]),
        )
        return r1, r2

    r1, r2 = asyncio.run(main())
    assert fake.verify_calls == 1, "both submissions must share one program"
    assert fake.verify_lane_count == 2  # the malformed lane never ships
    assert r1 == [True, False]
    assert r2 == [True]
    assert plane.coalesced_flushes == 1


def test_flush_failure_degrades_msm_and_retries():
    """A device failure during a flush is not a crypto verdict: the
    coalescer flips the MSM family off, rebuilds the plane via the
    factory, and retries the SAME batch — waiters get results, not
    errors (the msm-off rung, mirroring tbls/tpu_impl._rlc_guarded)."""
    from charon_tpu.ops import msm as MSM

    impl = PythonImpl()

    class BoomPlane(FakePlane):
        def verify_host(self, pks, msgs, sigs, rng=None):
            raise RuntimeError("MOSAIC lowering failed")

    good = FakePlane(T)
    plane = SlotCoalescer(
        BoomPlane(T), window=0.01, plane_factory=lambda: good
    )

    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    root = b"\x55" * 32
    sig = impl.sign(sk, root)

    try:
        assert MSM.msm_active()
        res = asyncio.run(plane.verify([(pk, root, sig)]))
        assert res == [True]
        assert good.verify_calls == 1, "retry must run on the rebuilt plane"
        assert MSM.msm_active() is False, "rung must flip the family off"
        assert plane.plane is good
    finally:
        MSM.set_msm(None)


def test_flush_failure_after_spent_rung_serves_host_fallback():
    """Once the msm-off rung is spent (the rebuilt plane fails too), the
    batch is served by the pure-python spec oracle instead of failing
    the waiters: a wedged accelerator costs latency, never the duty
    (the degradation ladder's last rung — ISSUE 2 graceful
    degradation)."""
    from charon_tpu.ops import msm as MSM

    impl = PythonImpl()

    class BoomPlane(FakePlane):
        def verify_host(self, pks, msgs, sigs, rng=None):
            raise RuntimeError("still broken")

    plane = SlotCoalescer(
        BoomPlane(T), window=0.01, plane_factory=lambda: BoomPlane(T)
    )

    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    root = b"\x66" * 32
    sig = impl.sign(sk, root)

    try:
        res = asyncio.run(plane.verify([(pk, root, sig)]))
        assert res == [True]
        assert plane.host_fallback_flushes == 1
        assert MSM.msm_active() is False
        # the oracle really verifies: a bad signature still fails
        res = asyncio.run(plane.verify([(pk, b"\x67" * 32, sig)]))
        assert res == [False]
    finally:
        MSM.set_msm(None)


def test_recombine_decode_failure_isolated():
    """A duty carrying an undecodable partial fails alone; a concurrent
    healthy duty still aggregates in the same flush."""
    impl = PythonImpl()
    tbls.set_implementation(impl)
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=0.01)

    pk1, _, psigs1, _, want1, ps1 = _duty_workload(impl, slot=9)
    pk2, _, psigs2, _, _, ps2 = _duty_workload(impl, slot=9)
    # corrupt duty 2's first partial beyond decompression
    psigs2[0] = d.ParSignedData(
        data=psigs2[0].data.with_signature(b"\xff" * 96),
        share_idx=psigs2[0].share_idx,
    )

    pubshares_by_idx = {
        i: {pk1: ps1[i], pk2: ps2[i]} for i in (1, 2, 3, 4)
    }
    agg = SigAgg(
        threshold=T, fork=FORK, plane=plane, pubshares_by_idx=pubshares_by_idx
    )
    out: dict = {}

    async def on_agg(duty, data_set):
        out.update(data_set)

    agg.subscribe(on_agg)

    async def main():
        ok, err = await asyncio.gather(
            agg.aggregate(Duty(9, DutyType.ATTESTER), {pk1: psigs1}),
            agg.aggregate(Duty(9, DutyType.SYNC_MESSAGE), {pk2: psigs2}),
            return_exceptions=True,
        )
        return ok, err

    ok, err = asyncio.run(main())
    assert ok is None
    assert isinstance(err, AggregationError)
    assert out[pk1].signature == want1
    assert fake.recombine_calls == 1
    assert fake.recombine_lane_count == 1  # only the healthy lane shipped


def test_verifier_async_routes_through_plane():
    """Eth2Verifier.verify_async uses the plane when installed and falls
    back to the synchronous tbls path when not."""
    impl = PythonImpl()
    tbls.set_implementation(impl)
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=0.01)

    pk, _, psigs, _, _, ps = _duty_workload(impl, slot=7)
    pubshares_by_idx = {i: {pk: ps[i]} for i in (1, 2, 3, 4)}

    with_plane = Eth2Verifier(FORK, pubshares_by_idx, plane=plane)
    without = Eth2Verifier(FORK, pubshares_by_idx)
    duty = Duty(7, DutyType.ATTESTER)

    async def main():
        assert await with_plane.verify_async(duty, {pk: psigs[0]})
        assert await without.verify_async(duty, {pk: psigs[0]})
        # unknown share index is rejected before any crypto
        bad = d.ParSignedData(data=psigs[0].data, share_idx=9)
        assert not await with_plane.verify_async(duty, {pk: bad})

    asyncio.run(main())
    assert fake.verify_calls == 1


def test_host_bug_errors_do_not_burn_the_msm_rung():
    """A host-side bug class (TypeError etc.) escaping the flush must NOT
    permanently disable the process-wide MSM fast path — the per-lane
    path would hit the same bug (ADVICE r4: gate the rung on
    device/compile error types)."""
    from charon_tpu.ops import msm as MSM

    impl = PythonImpl()

    class BuggyPlane(FakePlane):
        def verify_host(self, pks, msgs, sigs, rng=None):
            raise TypeError("tracer shape bug")

    plane = SlotCoalescer(
        BuggyPlane(T), window=0.01, plane_factory=lambda: FakePlane(T)
    )

    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    root = b"\x77" * 32
    sig = impl.sign(sk, root)

    try:
        assert MSM.msm_active()
        # the batch is still served — by the python-spec oracle, which
        # is a different code path from the buggy plane — but the MSM
        # family stays on and the plane is never rebuilt
        res = asyncio.run(plane.verify([(pk, root, sig)]))
        assert res == [True]
        assert plane.host_fallback_flushes == 1
        assert MSM.msm_active(), "host bug must not flip the MSM family"
    finally:
        MSM.set_msm(None)


def test_dispatch_gate_queues_flush_until_tuner_settles():
    """app/run.py wires the autotune tune_done event in as
    dispatch_gate: a flush whose window closes while the boot-time
    tuner is still flipping the kernel dispatch flags must QUEUE behind
    the gate (and keep coalescing late arrivals) instead of racing the
    trial configs and churning freshly compiled executables."""
    impl = PythonImpl()
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=0.01)

    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    root = b"\x88" * 32
    sig = impl.sign(sk, root)

    async def main():
        gate = asyncio.Event()
        plane.dispatch_gate = gate
        t1 = asyncio.create_task(plane.verify([(pk, root, sig)]))
        await asyncio.sleep(0.05)  # window long elapsed, gate still down
        assert fake.verify_calls == 0, "flush must wait for the tuner"
        assert not t1.done()
        # a submission arriving during the gated window joins the SAME
        # armed flush rather than arming another one behind it
        t2 = asyncio.create_task(plane.verify([(pk, root, sig)]))
        await asyncio.sleep(0.02)
        gate.set()
        return await asyncio.gather(t1, t2)

    r1, r2 = asyncio.run(main())
    assert r1 == [True] and r2 == [True]
    assert plane.gated_flushes == 1
    assert fake.verify_calls == 1, "gated submissions share one program"
    assert fake.verify_lane_count == 2


def test_no_dispatch_gate_means_no_gating():
    """Coalescers without a wired gate (tests, CLI tools, tbls off)
    flush exactly as before."""
    impl = PythonImpl()
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=0.01)
    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    sig = impl.sign(sk, b"\x99" * 32)
    assert asyncio.run(plane.verify([(pk, b"\x99" * 32, sig)])) == [True]
    assert plane.gated_flushes == 0


# -- what closes a window (ISSUE 27) ------------------------------------------
#
# A submission may say which wave it belongs to and how many submissions
# that wave expects; the window closes "complete" the moment every wave
# in it is whole and nothing is still decoding. The coalescer's clock
# stands still in these tests and its timer is a year long on the real
# one, so a window closes on its timer only when a test says so: no
# assertion here races the wall clock.

from charon_tpu.core import cryptoplane as _cp
from charon_tpu.crypto import g1g2
from charon_tpu.testutil.waiting import wait_until

YEAR = 3.0e7


class _StillClock:
    """Stands where the `time` module stands in core/cryptoplane."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now

    def time(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = _StillClock()
    monkeypatch.setattr(_cp, "time", c)
    return c


def _ring_timer(coal, clock):
    """The armed window's timer runs out."""
    clock.now = coal._flush_at + 0.001
    coal._flush_wake.set()


def _lane(root=b"\x07" * 32):
    return (g1g2.g1_to_bytes(g1g2.G1_GEN), root, g1g2.g2_to_bytes(g1g2.G2_GEN))


def _coalescer(**kw):
    fake, stats = FakePlane(T), []
    kw.setdefault("decode_workers", 0)  # decode inline: a submission is
    # in the queue by the time its task first yields
    coal = SlotCoalescer(
        fake, window=YEAR, window_max=2 * YEAR, stats_hook=stats.append, **kw
    )
    return coal, fake, stats


async def _settle(turns=5):
    for _ in range(turns):
        await asyncio.sleep(0)


async def _all(*aws):
    return await asyncio.wait_for(asyncio.gather(*aws), 30)


def test_whole_wave_closes_complete_and_runs_one_program(clock):
    coal, fake, stats = _coalescer()
    wave = (("duty-5", 4),)

    async def main():
        return await _all(*(coal.verify([_lane()], wave=wave) for _ in range(4)))

    assert asyncio.run(main()) == [[True]] * 4
    assert fake.verify_calls == 1 and fake.verify_lane_count == 4
    (s,) = stats
    assert s.window_closed_by == "complete" and s.jobs == 4
    assert s.window_span == (1000.0, 1000.0), "nothing was waited out"
    assert s.window == YEAR, "the window CONFIGURED travels as before"
    assert coal.windows_closed == {"complete": 1}


def test_wave_one_set_short_waits_for_the_timer(clock):
    """A silent operator: n - 1 of n sets. Never close on fewer than the
    wave expects (the straggler would flush alone, on another bucket)."""
    coal, fake, stats = _coalescer()
    wave = (("duty-5", 4),)

    async def main():
        jobs = [asyncio.create_task(coal.verify([_lane()], wave=wave)) for _ in range(3)]
        await _settle()
        assert len(coal._verify_q) == 3 and fake.verify_calls == 0
        assert not coal._flush_task.done(), "a short wave keeps its window open"
        _ring_timer(coal, clock)
        return await _all(*jobs)

    assert asyncio.run(main()) == [[True]] * 3
    assert fake.verify_calls == 1
    (s,) = stats
    assert s.window_closed_by == "timer" and s.jobs == 3
    assert s.window_span[1] - s.window_span[0] == pytest.approx(YEAR, rel=1e-6)
    assert coal.windows_closed == {"timer": 1}


@pytest.mark.parametrize("second", ["verify", "recombine"])
def test_window_stays_open_until_every_wave_in_it_is_whole(clock, second):
    """Two duties in one window, one whole and one not: the window waits
    for the second. Verify and recombine jobs of one key are counted
    apart, so a duty's recombine job never completes its verify wave."""
    impl = PythonImpl()
    tbls.set_implementation(impl)
    coal, fake, stats = _coalescer()
    pk, gpk, psigs, root, _, ps = _duty_workload(impl, slot=5)
    idx = [1, 2, 3]
    row = dict(
        pubshares=[[ps[i] for i in idx]], roots=[root],
        partials=[[p.data.signature for p in psigs]], group_pks=[gpk], indices=[idx],
    )

    async def main():
        whole = [asyncio.create_task(coal.verify([_lane()], wave=(("A", 2),))) for _ in range(2)]
        half = asyncio.create_task(coal.verify([_lane()], wave=(("B", 2),)))
        if second == "recombine":
            # B's recombine job is whole by itself and adds nothing to
            # B's verify wave
            other = asyncio.create_task(coal.recombine(**row, wave=(("B", 1),)))
        await _settle()
        assert fake.verify_calls == 0 and not coal._flush_task.done()
        last = asyncio.create_task(coal.verify([_lane()], wave=(("B", 2),)))
        out = await _all(*whole, half, last)
        if second == "recombine":
            await asyncio.wait_for(other, 30)
        return out

    assert asyncio.run(main()) == [[True]] * 4
    assert fake.verify_calls == 1 and fake.verify_lane_count == 4
    (s,) = stats
    assert s.window_closed_by == "complete"
    assert s.jobs == (5 if second == "recombine" else 4)


def test_submission_still_decoding_joins_the_complete_flush(clock, monkeypatch):
    """The count completes while another submission is on the decode
    pool: the window waits for it and both leave in ONE program."""
    import threading

    coal, fake, stats = _coalescer(decode_workers=2)
    gate, slow_root = threading.Event(), b"\x09" * 32
    decode = _cp._decode_verify_lane

    def held(item):
        if item[1] == slow_root:
            assert gate.wait(30)
        return decode(item)

    monkeypatch.setattr(_cp, "_decode_verify_lane", held)

    async def main():
        slow = asyncio.create_task(coal.verify([_lane(slow_root)], wave=(("B", 1),)))
        await _settle()
        fast = [asyncio.create_task(coal.verify([_lane()], wave=(("A", 2),))) for _ in range(2)]
        await wait_until(lambda: len(coal._verify_q) >= 2, "both decoded and counted")
        await _settle()
        assert fake.verify_calls == 0 and not coal._flush_task.done(), (
            "wave A is whole, but a submission is still decoding")
        gate.set()
        return await _all(slow, *fast)

    try:
        assert asyncio.run(main()) == [[True]] * 3
    finally:
        gate.set()
        coal.close()
    assert fake.verify_calls == 1, "no split"
    (s,) = stats
    assert s.window_closed_by == "complete" and s.jobs == 3


def test_hinted_recombine_flushes_after_its_decode(clock):
    """SigAgg says its job is the duty's one recombine job: the window
    holds nothing that could still come, so it does not wait."""
    impl = PythonImpl()
    tbls.set_implementation(impl)
    coal, fake, stats = _coalescer(decode_workers=1)
    pk, gpk, psigs, root, want, ps = _duty_workload(impl, slot=5)
    agg = SigAgg(
        threshold=T, fork=FORK, plane=coal,
        pubshares_by_idx={i: {pk: ps[i]} for i in (1, 2, 3, 4)},
    )
    out: dict = {}

    async def on_agg(duty, data_set):
        out.update(data_set)

    agg.subscribe(on_agg)
    try:
        asyncio.run(asyncio.wait_for(agg.aggregate(Duty(5, DutyType.ATTESTER), {pk: psigs}), 30))
    finally:
        coal.close()
    assert out[pk].signature == want and fake.recombine_calls == 1
    (s,) = stats
    assert s.window_closed_by == "complete" and s.jobs == 1
    assert s.decode_spans and s.window_span[0] >= s.decode_spans[-1][1]


@pytest.mark.parametrize("hinted", [0, 2])
def test_job_without_a_hint_closes_its_window_as_before(clock, hinted):
    """No hint, no change: the timer closes the window and the controller
    adapts — also where a whole hinted wave shares the window with it."""
    coal, fake, stats = _coalescer()

    async def main():
        jobs = [asyncio.create_task(coal.verify([_lane()])) for _ in range(2)]
        jobs += [
            asyncio.create_task(coal.verify([_lane()], wave=(("A", hinted),)))
            for _ in range(hinted)
        ]
        await _settle()
        assert fake.verify_calls == 0 and not coal._flush_task.done()
        _ring_timer(coal, clock)
        return await _all(*jobs)

    assert asyncio.run(main()) == [[True]] * (2 + hinted)
    (s,) = stats
    assert s.window_closed_by == "timer" and s.jobs == 2 + hinted
    assert coal.current_window == pytest.approx(YEAR * coal.WINDOW_GROW)
    assert coal.windows_closed == {"timer": 1}


def test_complete_close_leaves_the_adaptive_window_as_it_was(clock):
    """A wave that came whole is no evidence that waiting longer catches
    more, nor that traffic thinned: neither grow nor decay."""
    coal, fake, stats = _coalescer()

    async def whole(key):
        await _all(*(coal.verify([_lane()], wave=((key, 3),)) for _ in range(3)))

    async def main():
        await whole("A")  # three jobs: a timer close would grow the window
        assert coal.current_window == YEAR
        jobs = [asyncio.create_task(coal.verify([_lane()])) for _ in range(2)]
        await _settle()
        _ring_timer(coal, clock)
        await _all(*jobs)
        grown = coal.current_window
        assert grown == pytest.approx(YEAR * coal.WINDOW_GROW)
        await _all(coal.verify([_lane()], wave=(("B", 1),)))  # one job: a timer close would decay it
        assert coal.current_window == grown

    asyncio.run(main())
    assert [s.window_closed_by for s in stats] == ["complete", "timer", "complete"]
    assert stats[2].window == pytest.approx(YEAR * coal.WINDOW_GROW)


def test_a_deadline_still_caps_a_window_whose_wave_is_short(clock):
    coal, fake, stats = _coalescer()

    async def main():
        job = asyncio.create_task(
            coal.verify([_lane()], wave=(("A", 2),), deadline=clock.now + 1.0))
        await _settle()
        assert coal._flush_at == pytest.approx(clock.now + 0.01)  # 1 % of what is left
        _ring_timer(coal, clock)
        return await asyncio.wait_for(job, 30)

    assert asyncio.run(main()) == [True]
    assert stats[0].window_closed_by == "deadline"


def test_vc_submission_and_peer_sets_make_one_wave(clock):
    """The call sites agree on the key: the VC's request (ValidatorAPI)
    and the n - 1 peers' sets (ParSigEx's verifier) of one duty and one
    set of validators are ONE wave of n, through a tenant's handle."""
    from charon_tpu.core.cryptosvc import CryptoPlaneService
    from charon_tpu.core.types import PubKey
    from charon_tpu.core.validatorapi import ValidatorAPI

    coal, fake, stats = _coalescer()
    svc = CryptoPlaneService(coal, round_interval=0.001)
    plane = svc.register("cluster-a")
    pk = PubKey("0x" + "ab" * 48)
    share, sig = g1g2.g1_to_bytes(g1g2.G1_GEN), g1g2.g2_to_bytes(g1g2.G2_GEN)
    n = 4
    pubshares_by_idx = {i: {pk: share} for i in range(1, n + 1)}
    verifier = Eth2Verifier(FORK, pubshares_by_idx, plane=plane)
    vapi = ValidatorAPI(1, pubshares_by_idx[1], FORK, plane=plane, roster=verifier.roster)
    duty = Duty(64, DutyType.RANDAO)

    def peer_set(idx):
        return {pk: d.ParSignedData(data=d.SignedData("randao", 2, sig), share_idx=idx)}

    async def main():
        peers = [asyncio.create_task(verifier.verify_async(duty, peer_set(i))) for i in (2, 3)]
        mine = asyncio.create_task(vapi.submit_randao(duty.slot, pk, sig))
        await _settle(20)
        assert len(coal._verify_q) == 3 and fake.verify_calls == 0, "3 of 4: wait"
        last = asyncio.create_task(verifier.verify_async(duty, peer_set(4)))
        assert await _all(*peers, last) == [True] * 3
        await asyncio.wait_for(mine, 30)

    try:
        asyncio.run(main())
    finally:
        svc.close()
        coal.close()
    assert fake.verify_calls == 1 and fake.verify_lane_count == n
    (s,) = stats
    assert s.window_closed_by == "complete" and s.jobs == n
    assert s.tenant_lanes == (("cluster-a", n),)


# -- one kind a flush (ISSUE 39) ----------------------------------------------
#
# Two kinds of duty triggered at the same instant share the armed window
# and nothing else: the wave keys say which kind a job belongs to (the type
# of the Duty they hold), each kind has its own timer and leaves as a flush
# of its own when ITS waves are whole.

import random

_BAD_SIG = b"\xff" * 96  # no G2 encoding: the lane fails on the host


def _two_waves():
    """An attester wave of 4 sets of 3 lanes and a sync-message wave of 4
    sets of 5 lanes, one lane of the sync wave's third set malformed; each
    set hinted as the node's submitters hint it."""
    from charon_tpu.core.parsigex import WaveSet

    everyone = frozenset({1, 2, 3, 4})
    waves = {}
    for duty, lanes in ((Duty(7, DutyType.ATTESTER), 3), (Duty(7, DutyType.SYNC_MESSAGE), 5)):
        key = (duty, frozenset(range(lanes)))
        waves[str(duty.type)] = [
            ([_lane(bytes([sender, i]) * 16) for i in range(lanes)],
             ((key, WaveSet(sender, everyone, 4)),))
            for sender in (1, 2, 3, 4)
        ]
    items, hint = waves["sync_message"][2]
    pk, root, _sig = items[1]
    items[1] = (pk, root, _BAD_SIG)
    return waves


def _submit_all(coal, jobs):
    return [asyncio.create_task(coal.verify(items, wave=hint)) for items, hint in jobs]


def _alone(kind):
    """What a window that holds `kind`'s wave alone gives: (verdicts by
    set, the flush's lanes, jobs and sets)."""
    coal, fake, stats = _coalescer()

    async def main():
        return await _all(*_submit_all(coal, _two_waves()[kind]))

    verdicts = asyncio.run(main())
    (s,) = stats
    assert s.window_closed_by == "complete" and s.duty_types == (kind,)
    return verdicts, (s.lanes, s.jobs, s.sets_expected, s.sets_seen, s.sets_awaited)


@pytest.mark.parametrize("shuffle", range(12))
def test_two_kinds_entering_one_window_leave_as_a_flush_each(clock, shuffle):
    """Every interleaving of the two waves' arrival: two flushes, one a
    kind, each with the lanes, ledger and verdicts its wave gets alone."""
    coal, fake, stats = _coalescer()
    waves = _two_waves()
    order = [(kind, k) for kind, jobs in waves.items() for k in range(len(jobs))]
    random.Random(f"two-kinds/{shuffle}").shuffle(order)

    async def main():
        tasks = {}
        for kind, k in order:
            (tasks[kind, k],) = _submit_all(coal, [waves[kind][k]])
            await _settle(2)  # each job enters alone, in this order
        return {at: await asyncio.wait_for(t, 30) for at, t in tasks.items()}

    got = asyncio.run(main())
    assert fake.verify_calls == 2 and len(stats) == 2
    assert coal.windows_closed == {"complete": 2}
    for s in stats:
        (kind,) = s.duty_types  # one kind a flush
        verdicts, shape = _alone(kind)
        assert (s.lanes, s.jobs, s.sets_expected, s.sets_seen, s.sets_awaited) == shape
        assert [got[kind, k] for k in range(4)] == verdicts
        assert s.window_closed_by == "complete" and s.window_parts == 1
    # each window closed the moment ITS wave was whole; on the device the
    # smaller wave goes first if the window knew of it by then (a whole
    # sync wave yields its turn to an attester wave still collecting)
    at = {kind: [i for i, (k, _) in enumerate(order) if k == kind] for kind in waves}
    sync_first = max(at["sync_message"]) < min(at["attester"])
    assert [s.duty_types[0] for s in stats] == (
        ["sync_message", "attester"] if sync_first else ["attester", "sync_message"])
    # and a flush that waited says for whom: the attester wave for nobody,
    # ever; the sync wave for the attester's if it was whole first
    assert stats[0].turn_yielded_to == ""
    assert stats[1].turn_yielded_to in ("", "attester") and coal.turns_yielded <= 1
    assert got["sync_message", 2] == [True, False, True, True, True]


def test_a_short_kind_waits_out_its_own_timer_beside_a_whole_one(clock):
    """The attester wave is whole and leaves; the sync wave, one set short,
    keeps ITS timer (from its own first job) and leaves when that runs out
    — neither waits for the other, neither takes the other's lanes."""
    coal, fake, stats = _coalescer()
    waves = _two_waves()

    async def main():
        sync = _submit_all(coal, waves["sync_message"][:3])
        await _settle()
        clock.now += 5.0  # the attester's first job comes later
        att = await _all(*_submit_all(coal, waves["attester"]))
        assert fake.verify_calls == 1 and not coal._flush_task.done()
        assert len(coal._verify_q) == 3 and set(coal._timers) == {"sync_message"}
        assert coal._flush_at == pytest.approx(1000.0 + YEAR)  # its own first job's
        _ring_timer(coal, clock)
        return att, await _all(*sync)

    att, sync = asyncio.run(main())
    assert att == [[True] * 3] * 4 and [len(v) for v in sync] == [5, 5, 5]
    first, second = stats
    assert (first.duty_types, first.window_closed_by, first.lanes) == (("attester",), "complete", 12)
    assert (second.duty_types, second.window_closed_by, second.lanes) == (
        ("sync_message",), "timer", 14)
    assert (second.sets_expected, second.sets_seen, second.sets_awaited) == (4, 3, 4)
    assert coal.windows_closed == {"complete": 1, "timer": 1} and coal.windows_split == 0


def test_kinds_that_close_in_the_same_instant_are_parts_of_one_close(clock):
    """Both kinds fall to timers that have run out when the window wakes:
    one close, two flushes, each saying it is one of 2 parts; both are
    ready while the device lane is busy, and it takes the smaller wave
    first though the larger came first."""
    import threading

    coal, fake, stats = _coalescer()
    waves = _two_waves()
    busy = threading.Event()

    async def main():
        jobs = _submit_all(coal, waves["sync_message"][:3] + waves["attester"][:3])
        await _settle()
        assert set(coal._timers) == {"attester", "sync_message"}
        coal._executor.submit(busy.wait)  # a program still on the device
        _ring_timer(coal, clock)
        await wait_until(lambda: len(coal._ready) >= 2, "both jobs ready")
        busy.set()
        return await _all(*jobs)

    asyncio.run(main())
    assert [(s.duty_types, s.window_parts, s.window_closed_by, s.lanes) for s in stats] == [
        (("attester",), 2, "timer", 9), (("sync_message",), 2, "timer", 14)]
    assert coal.windows_split == 1 and coal.windows_closed == {"timer": 2}


def test_a_whole_kind_leaves_while_another_kinds_close_waits_for_its_decode(clock, monkeypatch):
    """A kind's close is a task of its own: the sync kind's timer has run
    out with one of its submissions still on the decode pool, and its
    close waits for that one; the attester wave that becomes whole in the
    meantime leaves at once, and the sync flush still takes its late job."""
    import threading

    coal, fake, stats = _coalescer(decode_workers=2)
    waves = _two_waves()
    gate, slow_root = threading.Event(), waves["sync_message"][3][0][4][1]  # a lane the attester wave has not
    decode = _cp._decode_verify_lane

    def held(item):
        if item[1] == slow_root:
            assert gate.wait(30)
        return decode(item)

    monkeypatch.setattr(_cp, "_decode_verify_lane", held)

    async def main():
        sync = _submit_all(coal, waves["sync_message"][:3])
        await wait_until(lambda: len(coal._verify_q) >= 3, "three sync sets decoded")
        sync += _submit_all(coal, waves["sync_message"][3:])  # held in its decode
        await _settle()
        _ring_timer(coal, clock)
        await _settle()
        assert coal._timers["sync_message"].closing and fake.verify_calls == 0
        att = await _all(*_submit_all(coal, waves["attester"]))
        assert fake.verify_calls == 1 and coal._timers["sync_message"].closing
        gate.set()
        return att, await _all(*sync)

    try:
        att, sync = asyncio.run(main())
    finally:
        gate.set()
        coal.close()
    assert att == [[True] * 3] * 4 and [len(v) for v in sync] == [5, 5, 5, 5]
    assert [(s.duty_types, s.window_closed_by, s.jobs, s.lanes) for s in stats] == [
        (("attester",), "complete", 4, 12), (("sync_message",), "timer", 4, 19)]  # one lane malformed


@pytest.mark.parametrize("comes", [True, False], ids=["its-last-set-comes", "its-timer-runs-out"])
def test_a_whole_wave_yields_its_turn_to_a_smaller_wave_still_collecting(clock, comes):
    """The sync wave is whole while the attester wave, due at the same
    instant and a fraction of its lanes, has three of its four sets in:
    the sync window closes `complete` at once, and its flush asks for the
    device only when the attester's has — so the order on the device is
    `_urgency`'s and not the order in which the last sets happened to
    come. It yields for as long as the other kind's timer at most."""
    coal, fake, stats = _coalescer()
    waves = _two_waves()

    async def main():
        att = _submit_all(coal, waves["attester"][:3])
        await _settle()
        sync = _submit_all(coal, waves["sync_message"])
        await _settle(20)
        assert coal.windows_closed == {"complete": 1} and set(coal._timers) == {"attester"}
        assert fake.verify_calls == 0 and len(coal._yielding) == 1  # closed, packed, yielding
        if comes:
            att += _submit_all(coal, waves["attester"][3:])
        else:
            _ring_timer(coal, clock)
        return await _all(*att), await _all(*sync)

    att, sync = asyncio.run(main())
    assert [len(v) for v in att] == [3] * (4 if comes else 3) and [len(v) for v in sync] == [5] * 4
    assert [(s.duty_types, s.window_closed_by, s.sets_seen) for s in stats] == [
        (("attester",), "complete" if comes else "timer", 4 if comes else 3),
        (("sync_message",), "complete", 4)]
    assert not coal._yielding and not coal._packing and coal.turns_yielded == 1
    assert [s.turn_yielded_s > 0 for s in stats] == [False, not comes]  # the still clock moved with the timer


def test_a_wave_is_judged_by_the_lanes_it_will_have_not_those_it_has(clock):
    """One sync set of four is in (5 lanes, fewer than the attester wave's
    12; the wave will have 20): the whole attester wave yields to nobody."""
    coal, fake, stats = _coalescer()
    waves = _two_waves()

    async def main():
        sync = _submit_all(coal, waves["sync_message"][:1])
        await _settle()
        assert coal._collecting_urgency("sync_message") == (float("inf"), 20)
        att = await _all(*_submit_all(coal, waves["attester"]))
        assert fake.verify_calls == 1 and set(coal._timers) == {"sync_message"}
        _ring_timer(coal, clock)
        return att, await _all(*sync)

    asyncio.run(main())
    assert [s.duty_types for s in stats] == [("attester",), ("sync_message",)]


def test_a_ready_flush_with_nothing_armed_beside_it_goes_at_once(clock):
    """No free device waits for a window that holds no set: the sync wave
    is whole before the attester wave's first set has come, so no attester
    timer is armed, and its flush is dispatched without a yield."""
    coal, fake, stats = _coalescer()
    waves = _two_waves()

    async def main():
        sync = await _all(*_submit_all(coal, waves["sync_message"]))
        assert fake.verify_calls == 1 and not coal._timers and not coal._yielding
        return sync, await _all(*_submit_all(coal, waves["attester"]))

    asyncio.run(main())
    assert [s.duty_types for s in stats] == [("sync_message",), ("attester",)]
    assert [(s.turn_yielded_s, s.turn_yielded_to) for s in stats] == [(0.0, "")] * 2
    assert coal.turns_yielded == 0


def test_a_set_still_on_the_decode_pool_counts_as_in(clock, monkeypatch):
    """The attester wave's first set was submitted before the sync wave
    closed but is still decoding (31-32 distinct roots to hash, where the
    sync wave's one root decodes at once): no attester timer is armed yet,
    and the packed sync flush yields all the same — the submission is on
    its way, with its deadline, its lanes and the sets its wave awaits. A
    decode that ends in no job wakes the flush that waited for it."""
    import threading

    coal, fake, stats = _coalescer(decode_workers=2)
    waves = _two_waves()
    gate, calls = threading.Event(), []
    decode = _cp._decode_verify_lane

    def held(item):
        calls.append(item)
        if len(calls) == 1:  # the attester set's first lane (the waves share roots)
            assert gate.wait(30)
        return decode(item)

    monkeypatch.setattr(_cp, "_decode_verify_lane", held)

    async def main():
        att = _submit_all(coal, waves["attester"][:1])  # held in its decode
        await _settle()
        assert not coal._timers and coal._collecting_urgency("attester") == (float("inf"), 12)
        sync = _submit_all(coal, waves["sync_message"])
        await wait_until(lambda: coal._yielding, "the flush yielding to the sync wave")
        assert fake.verify_calls == 0 and "attester" not in coal._timers
        assert coal.windows_closed == {"complete": 1}
        gate.set()
        att += _submit_all(coal, waves["attester"][1:])
        return await _all(*att, *sync)

    try:
        asyncio.run(main())
    finally:
        gate.set()
        coal.close()
    assert [(s.duty_types, s.turn_yielded_to) for s in stats] == [
        (("attester",), ""), (("sync_message",), "attester")]


def test_a_flush_says_how_long_it_yielded_and_to_which_kind(clock):
    coal, fake, stats = _coalescer()
    waves = _two_waves()

    async def main():
        att = _submit_all(coal, waves["attester"][:1])
        await _settle()
        sync = _submit_all(coal, waves["sync_message"])
        await _settle(20)
        assert fake.verify_calls == 0 and len(coal._yielding) == 1
        clock.now += 0.25  # the attester wave's other sets trail by a quarter second
        att += _submit_all(coal, waves["attester"][1:])
        return await _all(*att, *sync)

    asyncio.run(main())
    first, second = stats
    assert (first.duty_types, first.turn_yielded_s, first.turn_yielded_to) == (("attester",), 0.0, "")
    assert second.duty_types == ("sync_message",)
    assert second.turn_yielded_s == pytest.approx(0.25) and second.turn_yielded_to == "attester"


def test_a_flush_never_yields_to_its_own_kind(clock):
    """An attester flush closed by its timer is packed when the set it
    waited for comes after all (a straggler: one set, a fraction of its
    lanes): that set's window is not waited out. One kind of duty in the
    window is served as before the lane had an order."""
    coal, fake, stats = _coalescer()
    waves = _two_waves()
    seen = []
    more_urgent = coal._more_urgent

    def watched(kind, mine):
        seen.append((kind, set(coal._timers), more_urgent(kind, mine)))
        return seen[-1][2]

    coal._more_urgent = watched

    async def main():
        jobs = _submit_all(coal, waves["attester"][:3])
        await _settle()
        pack = coal._pack_part

        async def straggler_comes_while_it_packs(vq, rq):
            if len(vq) == 3:
                jobs.extend(_submit_all(coal, waves["attester"][3:]))
                await _settle()
            return await pack(vq, rq)

        coal._pack_part = straggler_comes_while_it_packs
        _ring_timer(coal, clock)
        await _settle(20)
        assert fake.verify_calls == 1, "the packed flush went; the straggler's window is armed"
        assert set(coal._timers) == {"attester"}
        _ring_timer(coal, clock)
        return await _all(*jobs)

    asyncio.run(main())
    assert seen[0] == ("attester", {"attester"}, set())
    assert [(s.sets_seen, s.window_closed_by, s.turn_yielded_s) for s in stats] == [
        (3, "timer", 0.0), (1, "timer", 0.0)]


@pytest.mark.parametrize("named", [True, False], ids=["senders-named", "a-count"])
def test_the_graded_deadline_cap_is_for_waves_that_name_nobody(clock, named):
    """1 % of what a duty has left caps the window of a job that does not
    say whom it waits for. A wave whose roster NAMES the senders still
    awaited has its window for them (and leaves the moment they are in):
    cut short it would leave in two, the trailing set on a bucket of its
    own. The deadline itself still bounds it."""
    from charon_tpu.core.parsigex import WaveSet

    coal, fake, stats = _coalescer()
    key = (Duty(7, DutyType.ATTESTER), frozenset({0}))
    everyone = frozenset({1, 2})

    def hint(sender):
        return ((key, WaveSet(sender, everyone, 2) if named else 2),)

    async def main():
        first = asyncio.create_task(
            coal.verify([_lane()], wave=hint(1), deadline=clock.now + 55.7))
        await _settle()
        assert coal._flush_at == pytest.approx(clock.now + (55.7 if named else 0.557))
        clock.now += 0.8  # the trailing set: later than the graded cap
        if not named:
            coal._flush_wake.set()
            await _settle()
        second = asyncio.create_task(
            coal.verify([_lane()], wave=hint(2), deadline=clock.now + 54.9))
        if not named:  # the first went alone; so does the second, on a cap of its own
            await _settle()
            assert coal._flush_at == pytest.approx(clock.now + 0.549)
            _ring_timer(coal, clock)
        return await _all(first, second)

    assert asyncio.run(main()) == [[True], [True]]
    assert [(s.jobs, s.window_closed_by) for s in stats] == (
        [(2, "complete")] if named else [(1, "deadline"), (1, "deadline")])


def test_a_flush_refused_its_turn_leaves_nothing_on_the_ready_heap():
    coal, _fake, _stats = _coalescer()

    async def main():
        coal._executor.shutdown()
        with pytest.raises(RuntimeError):
            await coal._on_device_lane((0.0, 1), lambda: 1)

    asyncio.run(main())
    assert coal._ready == []


def test_jobs_that_name_no_duty_share_a_kind_as_they_always_did(clock):
    """Keys that hold no Duty (tools, tests) and jobs with no hint are one
    kind, "": one window, one timer, one flush — the code before ISSUE 39."""
    coal, fake, stats = _coalescer()

    async def main():
        jobs = [asyncio.create_task(coal.verify([_lane()], wave=(("A", 1),))),
                asyncio.create_task(coal.verify([_lane()]))]
        await _settle()
        assert set(coal._timers) == {""} and fake.verify_calls == 0, "the unhinted job waits"
        _ring_timer(coal, clock)
        return await _all(*jobs)

    assert asyncio.run(main()) == [[True], [True]]
    (s,) = stats
    assert (s.jobs, s.duty_types, s.window_closed_by, s.sets_expected) == (2, (), "timer", None)
