"""Seeded chaos scenarios across the duty pipeline (ISSUE 2 tentpole).

Every scenario drives a real 4-node (t=3) in-process cluster through the
fault-injection plane (`testutil/chaos.py`) with a FIXED seed, and
asserts the distributed validator's core promise: the duty completes
t-of-n, or the tracker names the exact injected fault — never a
misattributed `insufficient_peer_signatures` on a duty that completed.

Scenarios (Handel-style adversarial schedules, PAPERS.md):
  1. silenced node            — VC down on one node
  2. minority partition+heal  — node 4 severed mid-run, then healed
  3. flappy beacon            — 5xx bursts + timeouts + stale head + slow
  4. crash-recover            — node crash-stops mid-run, restarts
  5. crypto-backend loss      — primary tbls backend dies; ladder degrades
  6. round-change storm       — QBFT under 20% message loss
  7. hedged slow beacon       — MultiClient races the runner-up on stall
  8. corrupt/duplicate frames — parsig transport mangles the wire

Progress-based deadlines (not one wall-clock bound): a 1-core CI box
under XLA-compile load can starve the event loop for long stretches; the
scenarios require fresh progress per window instead of raw speed.
"""

import asyncio
import time

import pytest

from charon_tpu import tbls
from charon_tpu.core.tracker import Reason, Step
from charon_tpu.core.types import Duty, DutyType
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.chaos import ChaosConfig, FlakyBackend
from charon_tpu.testutil.simnet import build_cluster
from charon_tpu.testutil.waiting import wait_progress

SEED = 20260803  # one seed for the whole suite: failures replay exactly


@pytest.fixture(autouse=True)
def host_tbls():
    # Prefer the native C++ backend (bit-compatible, ~20x faster) so the
    # chaos runs exercise realistic crypto latencies; fall back to Python.
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


def _atts_by_slot(beacon) -> dict[int, int]:
    out: dict[int, int] = {}
    for a in beacon.attestations:
        out[a.data.slot] = out.get(a.data.slot, 0) + 1
    return out


def _slots_with(beacon, count: int, after: int = -1) -> list[int]:
    return sorted(
        s
        for s, c in _atts_by_slot(beacon).items()
        if c >= count and s > after
    )


async def _stop(cluster, tasks):
    for node in cluster.nodes:
        node.scheduler.stop()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


def _start(cluster):
    return [
        asyncio.create_task(node.scheduler.run()) for node in cluster.nodes
    ]


# -- 1. silenced node --------------------------------------------------------


def test_chaos_silenced_node():
    """One VC down: the other three supply threshold partials, every
    node still broadcasts, and each healthy tracker names the silent
    share — per-validator attribution stays clean (no misattribution on
    the completed duty)."""

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.4,
            chaos=ChaosConfig(seed=SEED),
        )

        async def silent_attest(slot, defs):
            return None  # VC down: never submits a partial signature

        cluster.nodes[3].vmock.attest = silent_attest
        tasks = _start(cluster)
        beacon = cluster.beacon
        try:
            slots = await wait_progress(
                lambda: _slots_with(beacon, 4),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast (node 4's VC silent)",
            )
        finally:
            await _stop(cluster, tasks)

        duty = Duty(slots[0], DutyType.ATTESTER)
        report = await cluster.nodes[0].tracker.duty_expired(duty)
        assert report.success
        assert report.participation == {1: True, 2: True, 3: True, 4: False}
        assert not report.failed_pubkeys, "completed duty must not misattribute"
        assert not report.inconsistent_pubkeys

    asyncio.run(run())


# -- 2. minority partition + heal -------------------------------------------


def test_chaos_minority_partition_and_heal():
    """Node 4 is severed mid-run: the majority keeps completing duties
    3-of-4 and its trackers name node 4 absent; node 4's own tracker
    attributes ITS miss to missing peer partials (the true fault). After
    heal, all four complete again."""

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.4,
            chaos=ChaosConfig(seed=SEED),
        )
        tasks = _start(cluster)
        beacon = cluster.beacon
        try:
            # healthy warm-up: some slot completed by all four
            healthy = (await wait_progress(
                lambda: _slots_with(beacon, 4),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast, before the partition",
            ))[0]

            cluster.partition({1, 2, 3}, {4})
            cut_at = max(_atts_by_slot(beacon) or [0])
            # majority progress: a post-partition slot completed by the
            # three connected nodes (node 4 cannot assemble a threshold)
            part_slot = (await wait_progress(
                lambda: [
                    s
                    for s in _slots_with(beacon, 3, after=cut_at + 1)
                    if _atts_by_slot(beacon)[s] == 3
                ],
                probe=lambda: len(beacon.attestations),
                what="a slot after the cut that exactly the three connected nodes broadcast",
            ))[0]

            cluster.heal()
            healed_at = max(_atts_by_slot(beacon))
            healed_slot = (await wait_progress(
                lambda: _slots_with(beacon, 4, after=healed_at),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast, after the heal",
            ))[0]
        finally:
            await _stop(cluster, tasks)

        assert healthy < part_slot < healed_slot

        duty = Duty(part_slot, DutyType.ATTESTER)
        # a majority node completed the duty and names share 4 absent
        report = await cluster.nodes[0].tracker.duty_expired(duty)
        assert report.success
        assert report.participation[4] is False
        assert not report.failed_pubkeys
        # the partitioned node names the real fault: its own partial
        # stored, but no peer signatures crossed the partition
        isolated = await cluster.nodes[3].tracker.duty_expired(duty)
        assert not isolated.success
        assert isolated.failed_step in (
            Step.PARSIG_EX,
            Step.PARSIG_DB_THRESHOLD,
        )
        assert isolated.reason in (
            Reason.NO_PEER_SIGNATURES,
            Reason.INSUFFICIENT_PARTIALS,
        )
        assert isolated.participation.get(4) is True

    asyncio.run(run())


# -- 3. flappy beacon --------------------------------------------------------


def test_chaos_flappy_beacon():
    """Beacon endpoint injects 5xx bursts, timeouts, slow responses and
    stale-head votes: the deadline-aware retryers (fetch, broadcast) and
    the hardened scheduler keep completing duties t-of-n."""

    async def run():
        cfg = ChaosConfig(
            seed=SEED,
            bn_error=0.2,
            bn_burst_max=2,
            bn_timeout=0.05,
            bn_slow=0.1,
            bn_slow_secs=0.1,
            bn_stale_head=0.2,
        )
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.4, chaos=cfg
        )
        tasks = _start(cluster)
        beacon = cluster.beacon
        try:
            slots = await wait_progress(
                lambda: _slots_with(beacon, 4),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast through the flappy beacon",
            )
        finally:
            await _stop(cluster, tasks)

        assert beacon.injected_errors > 0, "seeded faults must have fired"
        report = await cluster.nodes[0].tracker.duty_expired(
            Duty(slots[0], DutyType.ATTESTER)
        )
        assert report.success
        assert not report.failed_pubkeys

    asyncio.run(run())


# -- 4. crash / recover ------------------------------------------------------


def test_chaos_crash_recover():
    """A node crash-stops mid-run: the cluster keeps completing duties
    3-of-4; after restart the node rejoins and a later slot completes
    4-of-4 (crash-only recovery on the same wired components)."""

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.4,
            chaos=ChaosConfig(seed=SEED),
        )
        tasks = _start(cluster)
        beacon = cluster.beacon
        try:
            (await wait_progress(
                lambda: _slots_with(beacon, 4),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast, before the crash",
            ))[0]

            cluster.crash_node(4)
            crash_at = max(_atts_by_slot(beacon))
            (await wait_progress(
                lambda: [
                    s
                    for s in _slots_with(beacon, 3, after=crash_at + 1)
                    if _atts_by_slot(beacon)[s] == 3
                ],
                probe=lambda: len(beacon.attestations),
                what="a slot after the crash that exactly the three live nodes broadcast",
            ))[0]

            restart_task = cluster.restart_node(4)
            tasks.append(restart_task)
            rejoin_at = max(_atts_by_slot(beacon))

            def fully_rejoined():
                # a post-restart slot completed by all four WHERE the
                # restarted node's own VC signed again (right after
                # restart it completes duties from peer partials alone —
                # correct, but not yet proof its whole stack is back)
                own = {
                    duty.slot
                    for (duty, _pk), sigs in cluster.nodes[
                        3
                    ].parsigdb._store.items()
                    if duty.type == DutyType.ATTESTER and 4 in sigs
                }
                return [
                    s
                    for s in _slots_with(beacon, 4, after=rejoin_at)
                    if s in own
                ]

            rejoined = (await wait_progress(
                fully_rejoined,
                probe=lambda: len(beacon.attestations),
                what="a slot after the restart that all four broadcast and node 4's own VC signed",
            ))[0]
        finally:
            await _stop(cluster, tasks)

        # the REJOINED node completed the post-restart duty itself: its
        # own partial is in, plus a threshold of peers (asserting node
        # 0's view of node 4's partial instead would race the last
        # cross-node delivery against the scheduler teardown)
        report = await cluster.nodes[3].tracker.duty_expired(
            Duty(rejoined, DutyType.ATTESTER)
        )
        assert report.success
        assert report.participation[4] is True
        assert sum(report.participation.values()) >= 3
        assert not report.failed_pubkeys

    asyncio.run(run())


# -- 5. crypto-backend loss --------------------------------------------------


def test_chaos_crypto_backend_loss():
    """The primary tbls backend dies mid-run (every op raises): the
    ResilientImpl ladder demotes it and serves the signing plane from
    the spec backend — duties keep completing, zero crypto downtime."""
    from charon_tpu.tbls.resilient import ResilientImpl

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.4,
            chaos=ChaosConfig(seed=SEED),
        )
        # swap the process backend AFTER setup: primary wedges on its
        # first post-swap op, the pure-python rung carries the duty
        flaky = FlakyBackend(
            tbls.get_implementation(), fail_after=0, seed=SEED
        )
        ladder = ResilientImpl([flaky, PythonImpl()], demote_after=2)
        tbls.set_implementation(ladder)

        tasks = _start(cluster)
        beacon = cluster.beacon
        try:
            slots = await wait_progress(
                lambda: _slots_with(beacon, 4),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast after the primary backend died",
            )
        finally:
            await _stop(cluster, tasks)

        assert flaky.injected_failures > 0
        assert ladder.demotions == [0], "primary rung must be demoted"
        assert ladder.fallback_calls > 0
        report = await cluster.nodes[0].tracker.duty_expired(
            Duty(slots[0], DutyType.ATTESTER)
        )
        assert report.success
        assert not report.failed_pubkeys

    asyncio.run(run())


# -- 6. round-change storm under message loss --------------------------------


def test_chaos_round_change_storm():
    """QBFT consensus under 20% seeded message loss: rounds change, the
    engine stays live, and duties still complete t-of-n (Handel:
    Byzantine-tolerant aggregation must be tested under adversarial
    schedules, not happy paths)."""

    async def run():
        cfg = ChaosConfig(seed=SEED, drop=0.2, delay=0.1, delay_max=0.05)
        cluster = build_cluster(
            n=4,
            t=3,
            num_validators=1,
            slot_duration=0.8,
            use_qbft=True,
            chaos=cfg,
        )
        tasks = _start(cluster)
        beacon = cluster.beacon
        try:
            slots = await wait_progress(
                lambda: _slots_with(beacon, 4),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast under 20 % QBFT message loss",
            )
        finally:
            await _stop(cluster, tasks)

        assert cluster.chaos_qbft.dropped > 0, "storm must have dropped frames"
        report = await cluster.nodes[0].tracker.duty_expired(
            Duty(slots[0], DutyType.ATTESTER)
        )
        assert report.success
        assert not report.failed_pubkeys

    asyncio.run(run())


# -- 7. hedged dispatch on a stalling beacon ---------------------------------


def test_chaos_hedged_slow_beacon():
    """MultiClient hedging: when the best endpoint stalls past its
    rolling-median latency, the runner-up is raced and the duty-critical
    call returns at fallback speed instead of burning the full timeout."""
    from charon_tpu.app.eth2wrap import MultiClient

    class Endpoint:
        def __init__(self, delay):
            self.delay = delay
            self.calls = 0

        async def attestation_data(self, slot, committee):
            self.calls += 1
            await asyncio.sleep(self.delay)
            return {"slot": slot, "delay": self.delay}

    async def run():
        primary, backup = Endpoint(0.01), Endpoint(0.02)
        mc = MultiClient([primary, backup], timeout=5.0)
        # build latency history on both endpoints (untried clients sort
        # first, and an empty window never hedges)
        await mc.attestation_data(1, 0)
        mc.errors[0] += 1
        await mc.attestation_data(2, 0)
        mc.errors[0] -= 1
        assert mc.best_idx == 0

        # the primary stalls far past its median: the hedge must win
        primary.delay = 3.0
        t0 = time.monotonic()
        out = await mc.attestation_data(3, 0)
        elapsed = time.monotonic() - t0
        assert out["delay"] == 0.02, "runner-up's answer must win"
        assert mc.hedged_total >= 1 and mc.hedge_wins >= 1
        assert elapsed < 2.0, "stall must cost ~hedge delay, not the stall"

    asyncio.run(run())


# -- 8. corrupted / duplicated / delayed parsig frames -----------------------


def test_chaos_corrupt_duplicate_parsig_frames():
    """The parsig wire mangles frames: corrupted sets are rejected by
    the Eth2Verifier before storage (never crash, never poison the
    tracker), duplicates dedup by share index, delays reorder. Duties
    still complete and the completed slot's report is clean."""

    async def run():
        cfg = ChaosConfig(
            seed=SEED, corrupt=0.2, duplicate=0.25, delay=0.2,
            delay_max=0.03,
        )
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.4, chaos=cfg
        )
        tasks = _start(cluster)
        beacon = cluster.beacon
        try:
            slots = await wait_progress(
                lambda: _slots_with(beacon, 4),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast over the mangled parsig wire",
            )
        finally:
            await _stop(cluster, tasks)

        transport = cluster.chaos_transport
        assert transport.corrupted > 0 and transport.duplicated > 0
        report = await cluster.nodes[0].tracker.duty_expired(
            Duty(slots[0], DutyType.ATTESTER)
        )
        assert report.success
        # corrupted frames were dropped at the verifier: they must not
        # surface as inconsistent partials or per-validator failures
        assert not report.inconsistent_pubkeys
        assert not report.failed_pubkeys

    asyncio.run(run())


# -- 9-11. multi-tenant crypto-plane isolation (ISSUE 8) ---------------------
#
# N independent DV clusters share one device mesh through the
# core/cryptosvc service boundary. Each scenario runs two tenants over
# one REAL SlotCoalescer (device = the counting FakePlane; forged lanes
# fail host decode exactly as they would in production) and asserts the
# tentpole promise: tenant A's abuse — forged-signature flood,
# crash-loop, queue flood, clock-skewed deadlines — costs tenant B
# ZERO duties, and the shed/breaker/quarantine counters attribute the
# damage to tenant A only.

from charon_tpu.core.cryptosvc import (  # noqa: E402
    CryptoPlaneService,
    PlaneOverloadError,
    TenantQuota,
)
from charon_tpu.testutil.chaos import SkewedClock, forged_signatures  # noqa: E402
from tests.test_cryptoplane import FakePlane, T  # noqa: E402


def _valid_items(n: int = 4):
    impl = PythonImpl()
    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    root = b"\x42" * 32
    sig = impl.sign(sk, root)
    return [(pk, root, sig)] * n


class _SharedMesh:
    """Two tenants over one real coalescer + service."""

    def __init__(self, breaker_cooldown: float = 0.3,
                 victim_quota: TenantQuota | None = None,
                 abuser_quota: TenantQuota | None = None):
        from charon_tpu.core.cryptoplane import SlotCoalescer

        self.fake = FakePlane(T)
        self.coal = SlotCoalescer(self.fake, window=0.01, decode_workers=2)
        self.svc = CryptoPlaneService(
            self.coal, round_lanes=64, round_interval=0.01
        )
        self.victim = self.svc.register(
            "tenant-b", victim_quota or TenantQuota()
        )
        self.abuser = self.svc.register(
            "tenant-a",
            abuser_quota
            or TenantQuota(
                breaker_window=64,
                breaker_min_lanes=16,
                breaker_threshold=0.5,
                breaker_cooldown=breaker_cooldown,
            ),
        )

    def close(self):
        self.svc.close()
        self.coal.close()

    def assert_damage_attributed_to_abuser_only(self):
        b = self.svc.tenant("tenant-b")
        assert b.breaker.state == "closed" and not b.breaker.transitions
        assert b.shed == {} and b.shed_lanes == 0
        assert b.quarantined_flushes == 0 and b.failed_lanes == 0


async def _run_victim_duties(
    plane, items, duties: int = 12, period: float = 0.03,
    budget: float = 2.0,
) -> int:
    """Tenant B's duty loop: paced verify bursts, each with a wall
    deadline AND a hard await budget. Returns duties missed."""
    missed = 0
    for _ in range(duties):
        t0 = time.monotonic()
        try:
            res = await asyncio.wait_for(
                plane.verify(list(items), deadline=time.time() + budget),
                timeout=budget,
            )
            ok = all(res) and (time.monotonic() - t0) <= budget
        except Exception:  # noqa: BLE001 — any failure = a missed duty
            ok = False
        if not ok:
            missed += 1
        await asyncio.sleep(period)
    return missed


def test_chaos_tenant_forged_flood_and_crash_loop():
    """THE acceptance scenario: tenant A pours forged-signature bursts
    into the shared plane while crash-looping (cancelling its own
    in-flight submissions); tenant B completes 100% of duties within
    deadline, A's breaker opens and quarantines it to its own flushes,
    and every damage counter names A."""

    async def run():
        mesh = _SharedMesh()
        rng = ChaosConfig(seed=SEED).stream("tenant:forged")
        items = _valid_items(4)
        pk, root, _sig = items[0]
        stop = asyncio.Event()

        async def one_burst():
            forged = [(pk, root, s) for s in forged_signatures(10, rng)]
            try:
                await mesh.abuser.verify(
                    forged, deadline=time.time() + 2.0
                )
            except PlaneOverloadError:
                pass

        async def crash_looping_flood():
            while not stop.is_set():
                task = asyncio.create_task(one_burst())
                await asyncio.sleep(rng.uniform(0.0, 0.01))
                if rng.random() < 0.5:
                    task.cancel()  # tenant A's node crashes mid-flight
                try:
                    await task
                except asyncio.CancelledError:
                    pass
                await asyncio.sleep(rng.uniform(0.0, 0.005))

        flood = asyncio.create_task(crash_looping_flood())
        try:
            missed = await _run_victim_duties(mesh.victim, items)
        finally:
            stop.set()
            await flood
        a = mesh.svc.tenant("tenant-a")
        assert missed == 0, f"tenant B missed {missed} duties"
        assert a.breaker.transitions.get("open", 0) >= 1
        assert a.quarantined_flushes > 0, "open breaker must quarantine A"
        assert a.failed_lanes > 0
        mesh.assert_damage_attributed_to_abuser_only()
        mesh.close()

    asyncio.run(run())


def test_chaos_tenant_queue_flood_sheds_only_flooder():
    """Tenant A floods the admission queue far over its lane bound:
    over-budget submissions shed fast with PlaneOverloadError (the
    flood never reaches the shared window), tenant B misses nothing,
    and only A's shed counters move."""

    async def run():
        mesh = _SharedMesh(
            abuser_quota=TenantQuota(
                max_queue_jobs=8, max_queue_lanes=64
            ),
        )
        rng = ChaosConfig(seed=SEED).stream("tenant:queueflood")
        items = _valid_items(4)
        stop = asyncio.Event()

        async def queue_flood():
            # fire-and-forget bursts WAY over quota, never awaiting
            # completion before the next — the classic queue flood
            pending: set[asyncio.Task] = set()
            while not stop.is_set():
                for _ in range(8):

                    async def burst():
                        try:
                            await mesh.abuser.verify(list(items) * 4)
                        except PlaneOverloadError:
                            pass

                    task = asyncio.create_task(burst())
                    pending.add(task)
                    task.add_done_callback(pending.discard)
                await asyncio.sleep(rng.uniform(0.001, 0.005))
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)

        flood = asyncio.create_task(queue_flood())
        try:
            missed = await _run_victim_duties(mesh.victim, items)
        finally:
            stop.set()
            await flood
        a = mesh.svc.tenant("tenant-a")
        assert missed == 0, f"tenant B missed {missed} duties"
        assert sum(a.shed.values()) > 0, "the flood must have shed"
        mesh.assert_damage_attributed_to_abuser_only()
        mesh.close()

    asyncio.run(run())


def test_chaos_tenant_clock_skewed_deadlines():
    """The host wall clock steps forward and backward (NTP correction,
    VM migration) while both tenants submit deadline-carrying work: the
    coalescer's per-window offset snapshot (the ISSUE 8 bugfix) keeps
    coalescing windows sane and tenant B misses zero duties."""

    async def run():
        mesh = _SharedMesh()
        rng = ChaosConfig(seed=SEED).stream("tenant:skew")
        items = _valid_items(4)
        stop = asyncio.Event()

        with SkewedClock() as clock:

            async def skewing_flood():
                while not stop.is_set():
                    clock.step(rng.uniform(-90.0, 90.0))
                    try:
                        await mesh.abuser.verify(
                            list(items), deadline=time.time() + 2.0
                        )
                    except PlaneOverloadError:
                        pass
                    await asyncio.sleep(rng.uniform(0.0, 0.01))

            flood = asyncio.create_task(skewing_flood())
            try:
                missed = await _run_victim_duties(mesh.victim, items)
            finally:
                stop.set()
                await flood
        assert missed == 0, f"tenant B missed {missed} duties"
        mesh.assert_damage_attributed_to_abuser_only()
        mesh.close()

    asyncio.run(run())
