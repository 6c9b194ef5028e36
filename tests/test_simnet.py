"""End-to-end in-process simnet: 4 nodes (t=3) complete an attestation duty
and every node broadcasts the same valid group signature.

Mirrors ref: testutil/integration/simnet_test.go:49-130 (attester flow with
beaconmock + validatormock), once with the echo consensus stub and once
with real QBFT consensus.
"""

import asyncio

import pytest

from charon_tpu import tbls
from charon_tpu.core.eth2data import SignedData
from charon_tpu.core.types import pubkey_to_bytes
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.simnet import build_cluster
from charon_tpu.testutil.waiting import wait_progress


@pytest.fixture(autouse=True)
def host_tbls():
    # Prefer the native C++ backend (bit-compatible, ~20x faster) so the
    # simnet exercises realistic crypto latencies; fall back to Python.
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


def _atts_completed_by_all(beacon, n: int = 4):
    """Slots for which all n nodes broadcast an attestation. Grouping by
    slot (instead of slicing the first n broadcasts) keeps the check
    correct when a starved event loop skews nodes across slot
    boundaries — the first n entries then MIX slots and carry different
    (all valid) signatures."""
    by_slot: dict[int, list] = {}
    for a in beacon.attestations:
        by_slot.setdefault(a.data.slot, []).append(a)
    return {s: atts for s, atts in by_slot.items() if len(atts) >= n}


def _props_completed_by_all(beacon, n: int = 4):
    by_slot: dict[int, list] = {}
    for proposal, sig in beacon.proposals:
        by_slot.setdefault(proposal.slot, []).append((proposal, sig))
    return {s: ps for s, ps in by_slot.items() if len(ps) >= n}


async def _drive_and_check(cluster):
    tasks = [
        asyncio.create_task(node.scheduler.run()) for node in cluster.nodes
    ]
    beacon = cluster.beacon
    try:

        await wait_progress(
            lambda: _atts_completed_by_all(beacon)
            and _props_completed_by_all(beacon),
            probe=lambda: (len(beacon.attestations), len(beacon.proposals)),
            what="an attestation slot and a proposal slot all four nodes broadcast",
        )
    finally:
        for node in cluster.nodes:
            node.scheduler.stop()
        await asyncio.gather(*tasks, return_exceptions=True)

    atts = next(iter(_atts_completed_by_all(beacon).values()))[:4]
    # all nodes recovered the SAME group signature
    sigs = {a.signature for a in atts}
    assert len(sigs) == 1
    # and it verifies under the group public key
    att = atts[0]
    group_pk = cluster.group_pubkeys[0]
    root = SignedData("attestation", att).signing_root(
        cluster.fork, att.data.slot // beacon.slots_per_epoch
    )
    tbls.verify(pubkey_to_bytes(group_pk), root, att.signature)

    # proposer flow: all nodes broadcast the same valid signed block
    props = next(iter(_props_completed_by_all(beacon).values()))[:4]
    psigs = {sig for _, sig in props}
    assert len(psigs) == 1
    proposal, psig = props[0]
    proot = SignedData("block", proposal).signing_root(
        cluster.fork, proposal.slot // beacon.slots_per_epoch
    )
    tbls.verify(pubkey_to_bytes(group_pk), proot, psig)


def test_simnet_attestation_flow():
    async def run():
        cluster = build_cluster(n=4, t=3, num_validators=1, slot_duration=0.4)
        await _drive_and_check(cluster)

    asyncio.run(run())


def test_simnet_attestation_flow_qbft():
    """Same flow with real QBFT consensus instead of the echo stub."""

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.8, use_qbft=True
        )
        await _drive_and_check(cluster)

    asyncio.run(run())


def test_simnet_survives_fuzzed_beacon():
    """Nightly-fuzz analogue (ref: testutil/compose/fuzz +
    beaconmock_fuzz.go): the beacon mock returns randomized shape-valid
    attestation data and injects synthetic errors, and the cluster must
    keep completing duties — consensus agrees on whatever the leader
    fetched, partials verify, broadcasts land."""

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.4, use_qbft=True
        )
        cluster.beacon.enable_fuzz(seed=3, error_rate=0.3)
        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        beacon = cluster.beacon
        try:

            # progress-based (testutil/waiting.py): a healthy run
            # finishes in ~2s; cold start + 30% injected errors +
            # exponential backoff come before anything lands
            await wait_progress(
                lambda: len(beacon.attestations) >= 4,
                probe=lambda: len(beacon.attestations),
                what="four attestations through the fuzzed beacon",
            )
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            for task in tasks:
                task.cancel()
        # every broadcast attestation carries a valid group signature
        # over the fuzzed (but agreed) data
        att = beacon.attestations[0]
        root = SignedData("attestation", att).signing_root(
            cluster.fork, att.data.slot // beacon.slots_per_epoch
        )
        group_pk = cluster.group_pubkeys[0]
        tbls.verify(pubkey_to_bytes(group_pk), root, att.signature)

    asyncio.run(run())


def test_simnet_tracker_names_silenced_node():
    """One node's VC goes silent; the cluster still completes the duty
    (3-of-4 threshold) and every healthy node's tracker NAMES the silent
    share in its participation report (VERDICT r3 next-step 5; ref:
    core/tracker/tracker.go analyseParticipation + the participation
    metrics the reference alerts on)."""

    async def run():
        cluster = build_cluster(n=4, t=3, num_validators=1, slot_duration=0.4)
        silenced = cluster.nodes[3]

        async def silent_attest(slot, defs):
            return None  # VC down: never submits a partial signature

        silenced.vmock.attest = silent_attest

        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        beacon = cluster.beacon
        try:

            # ALL FOUR nodes still broadcast for ONE slot: the silent
            # node's peers supply threshold partials, so its own
            # workflow completes (grouped by slot — see
            # _atts_completed_by_all)
            await wait_progress(
                lambda: _atts_completed_by_all(beacon),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast (node 4's VC silent)",
            )
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)

        from charon_tpu.core.types import Duty, DutyType

        # analyse a slot every node completed — the tracker on node 0
        # must have its own full event trail for it
        slot = next(iter(_atts_completed_by_all(beacon)))
        duty = Duty(slot, DutyType.ATTESTER)
        report = await cluster.nodes[0].tracker.duty_expired(duty)
        assert report.success
        # shares 1-3 participated; share 4 is named absent
        assert report.participation == {1: True, 2: True, 3: True, 4: False}
        assert report.participation_counts.get(4, 0) == 0
        assert report.participation_counts[1] == report.expected_per_peer == 1
        assert not report.unexpected_shares
        assert not report.inconsistent_pubkeys

    asyncio.run(run())


def test_simnet_priority_switches_protocol_mid_run():
    """Nodes start with DIFFERENT protocol preferences; the epoch-edge
    priority negotiation converges (count-first scoring) and every
    node's consensus implementation actually switches mid-run, after
    which duties keep completing (VERDICT r3 next-step 6; ref:
    core/priority + core/infosync + app/app.go:650-668)."""

    SLOTS_PER_EPOCH = 4

    async def run():
        # 3 nodes prefer echo, 1 prefers qbft -> echo wins 4:4 on count,
        # 3999:3997 on position tie-break
        prefs = [
            ["echo/1.0.0", "qbft/2.0.0"],
            ["echo/1.0.0", "qbft/2.0.0"],
            ["echo/1.0.0", "qbft/2.0.0"],
            ["qbft/2.0.0", "echo/1.0.0"],
        ]
        # The negotiation runs in the LAST slot of an epoch and needs
        # all four nodes' messages for that slot inside the
        # Prioritiser's 6 s: a scheduler whose starved loop skips that
        # one slot loses the whole epoch's round. A QBFT attester +
        # proposer wave costs the loop 0.4 CPU-s a slot (my sandbox,
        # PR 41): 1.5 s slots are what a tier-1 worker with a third of
        # a core serves; four of them an epoch keep the first
        # negotiation at 4.5 s.
        cluster = build_cluster(
            n=4,
            t=3,
            num_validators=1,
            slot_duration=1.5,
            slots_per_epoch=SLOTS_PER_EPOCH,
            use_qbft=True,
            protocol_prefs=prefs,
        )
        assert all(
            n.consensus.current_consensus().protocol_id == "qbft/2.0.0"
            for n in cluster.nodes
        )
        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        beacon = cluster.beacon
        try:

            def protocols():
                return [
                    n.consensus.current_consensus().protocol_id
                    for n in cluster.nodes
                ]

            # progress: an epoch's last slot ticked on some node is a
            # negotiation begun
            await wait_progress(
                lambda: protocols() == ["echo/1.0.0"] * 4,
                probe=lambda: (
                    protocols(),
                    sum(
                        1
                        for n in cluster.nodes
                        for s in n.ticked
                        if s % SLOTS_PER_EPOCH == SLOTS_PER_EPOCH - 1
                    ),
                ),
                what="every node's consensus switched to echo/1.0.0",
            )
            # duties still complete under the switched protocol
            base = len(beacon.attestations)
            await wait_progress(
                lambda: len(beacon.attestations) >= base + 4,
                probe=lambda: len(beacon.attestations),
                what="four more attestations under the switched protocol",
            )
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)

        # the post-switch attestations still carry valid group signatures
        att = beacon.attestations[-1]
        root = SignedData("attestation", att).signing_root(
            cluster.fork, att.data.slot // beacon.slots_per_epoch
        )
        tbls.verify(
            pubkey_to_bytes(cluster.group_pubkeys[0]), root, att.signature
        )

    asyncio.run(run())


def test_simnet_cross_slot_replay_attributed_to_channel():
    """Cross-slot replay: a consensus message captured from one duty and
    re-delivered under a DIFFERENT duty — or under its own duty but from
    the wrong channel peer — is dropped at the adapter boundary before
    any engine, transport, or value-cache state exists for it, and the
    evidence ledger names the CHANNEL peer, not the original signer
    (ISSUE 16 satellite: replay regression in the simnet path)."""

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.8, use_qbft=True
        )
        adapters = [
            node.consensus.current_consensus() for node in cluster.nodes
        ]
        assert adapters[0].protocol_id == "qbft/2.0.0"

        # tap the QBFT fabric: capture every frame crossing the net
        net = adapters[0].net
        captured = []
        orig_bcast = net.broadcast

        async def tap(from_idx, duty, msg, values, tctx=None):
            captured.append(msg)
            await orig_bcast(from_idx, duty, msg, values, tctx=tctx)

        net.broadcast = tap

        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:

            await wait_progress(
                lambda: captured,
                what="a consensus frame on the tapped QBFT fabric",
            )
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)

        from charon_tpu.core.types import Duty, DutyType

        victim = adapters[0]
        evidence = cluster.nodes[0].evidence
        # honest run: nothing was ever flagged as replay
        assert evidence.count(kind="qbft_replay") == 0

        msg = captured[0]
        # channel identities for the two replays: distinct from each
        # other AND from the original signer, so the attribution asserts
        # below can't collide
        adversary, wrong_channel = [
            i for i in range(4) if i != msg.source
        ][:2]

        # cross-slot replay: duty-A traffic re-delivered under duty B
        replay_duty = Duty(msg.instance.slot + 1000, DutyType.ATTESTER)
        instances_before = set(victim._instances)
        values_before = set(victim._values)
        victim.deliver(replay_duty, msg, {}, sender=adversary)
        assert evidence.count(peer=adversary + 1, kind="qbft_replay") == 1

        # stale replay on the RIGHT duty but the WRONG channel: the frame
        # carries an honest original signer, so only the channel can be
        # blamed — and it is
        victim.deliver(msg.instance, msg, {}, sender=wrong_channel)
        assert evidence.count(peer=wrong_channel + 1, kind="qbft_replay") == 1

        # the original signer was never framed by either replay
        assert evidence.count(peer=msg.source + 1, kind="qbft_replay") == 0
        # and no adapter state materialised for the replayed duty
        assert set(victim._instances) == instances_before
        assert set(victim._values) == values_before
        assert replay_duty not in victim._instances

    asyncio.run(run())
