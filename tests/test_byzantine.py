"""Seeded Byzantine adversary battery (ISSUE 16 tentpole).

Every scenario is deterministic from SEED, runs with f adversaries at or
below the faulty threshold, and asserts the full BFT contract:

  * **liveness** — honest nodes finalize (the scenario await completing
    IS the assertion; a liveness break times out);
  * **safety** — no two honest nodes decide different values, and no
    aggregate forms from conflicting partials;
  * **attribution** — every `byzantine_evidence` entry names ONLY
    adversary identities (PR 8 acceptance style: blaming an honest
    victim is the failure mode these tests exist to catch);
  * **conformance** — on the partial-signature path, every device-plane
    verify/recombine verdict is cross-checked lane-by-lane against the
    pure-python oracle (DifferentialTbls), zero mismatches.

Strategy catalogue (ci.sh chaos tier runs all of it):
  1. leader equivocation (conflicting PRE-PREPAREs broadcast)
  2. split equivocation (different values to different honest subsets)
  3. PREPARE/COMMIT equivocation by a non-leader
  4. forged PRE-PREPARE justification (round-2 leader, fake RC quorum)
  5. forged ROUND-CHANGE prepared-value injection
  6. cross-instance message replay
  7. ROUND-CHANGE flood against the per-sender stored bound
  8. framing resistance (garbage stamped with honest source indices)
  9. malformed protocol messages (non-leader PRE-PREPARE, oversized
     justification)
 10. parsigdb pending-set flood
 11. rogue partial-signature flood through simnet (differential)
 12. double-signed conflicting partials through simnet (differential,
     sigagg lane exclusion)
 13. selective-send partition through simnet
"""

import asyncio
import random
from dataclasses import replace

import pytest

from charon_tpu import tbls
from charon_tpu.core.qbft import Msg, MsgType
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.byzantine import (
    AdversaryParams,
    DifferentialTbls,
    assert_agreement,
    assert_evidence_only,
    assert_no_mismatches,
    deterministic_leader,
    differential_backend,
    find_instance,
    run_with_adversary,
)
from charon_tpu.testutil.waiting import wait_progress

SEED = 160808  # one seed drives the whole battery; change = new schedule

PARAMS = AdversaryParams(seed=SEED, n=4, t=3, f=1)
ADV = PARAMS.adversaries[0]


@pytest.fixture(autouse=True)
def host_tbls():
    # Same backend policy as test_simnet: native when available (fast,
    # bit-compatible), python otherwise — the differential wrapper then
    # cross-checks whichever is active against the python oracle.
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


# ---------------------------------------------------------------------------
# QBFT-plane strategies (pure harness)
# ---------------------------------------------------------------------------


def test_leader_equivocation_broadcast():
    """Strategy 1: the adversary leads round 1 and broadcasts two
    conflicting PRE-PREPAREs. First one wins at every honest node, the
    second is detected as equivocation and attributed."""
    inst = find_instance(4, 1, ADV, prefix="equiv")

    async def attack(net, signer, p):
        a = signer.sign(Msg(MsgType.PRE_PREPARE, inst, ADV, 1, "good"))
        b = signer.sign(Msg(MsgType.PRE_PREPARE, inst, ADV, 1, "evil"))
        net.inject_all(a)
        net.inject_all(b)

    res = asyncio.run(run_with_adversary(PARAMS, inst, attack))
    assert assert_agreement(res.decisions) == "good"
    assert_evidence_only(res.evidence, PARAMS.adversaries)
    assert res.evidence.count(peer=ADV, kind="qbft_equivocation") >= 1
    assert res.merged_drops()["equivocation"] >= 1


def test_split_equivocation_forces_round_change():
    """Strategy 2: conflicting PRE-PREPAREs to DIFFERENT honest subsets
    — no subset reaches a PREPARE quorum, the cluster round-changes to
    an honest leader and still agrees."""
    inst = find_instance(4, 1, ADV, prefix="split")
    # deterministic_leader advances round-robin: round 2 is honest
    assert deterministic_leader(4)(inst, 2) in PARAMS.honest

    async def attack(net, signer, p):
        a = signer.sign(Msg(MsgType.PRE_PREPARE, inst, ADV, 1, "va"))
        b = signer.sign(Msg(MsgType.PRE_PREPARE, inst, ADV, 1, "vb"))
        net.inject(0, a)
        net.inject(1, a)
        net.inject(2, b)

    res = asyncio.run(run_with_adversary(PARAMS, inst, attack))
    decided = assert_agreement(res.decisions)
    assert decided in {f"value-{i}" for i in PARAMS.honest}
    assert_evidence_only(res.evidence, PARAMS.adversaries)


def test_prepare_commit_equivocation():
    """Strategy 3: honest leader; the adversary sends conflicting
    PREPARE and COMMIT pairs. Detected at every honest node; the duty
    decides the leader's value regardless."""
    inst = find_instance(4, 1, 0, prefix="pcequiv")

    async def attack(net, signer, p):
        for typ in (MsgType.PREPARE, MsgType.COMMIT):
            m1 = signer.sign(Msg(typ, inst, ADV, 1, "x"))
            m2 = signer.sign(Msg(typ, inst, ADV, 1, "y"))
            net.inject_all(m1)
            net.inject_all(m2)

    res = asyncio.run(run_with_adversary(PARAMS, inst, attack))
    assert assert_agreement(res.decisions) == "value-0"
    assert_evidence_only(res.evidence, PARAMS.adversaries)
    assert res.evidence.count(peer=ADV, kind="qbft_equivocation") >= 1


def test_forged_preprepare_justification():
    """Strategy 4: the adversary leads round 2 and sends a round-2
    PRE-PREPARE justified by a FORGED round-change quorum (garbage
    signatures claiming honest sources). The outer signature verifies,
    the justification does not — evidence says the adversary forged it,
    never the claimed honest sources."""
    inst = find_instance(4, 2, ADV, prefix="forgejust")
    assert deterministic_leader(4)(inst, 1) in PARAMS.honest

    async def attack(net, signer, p):
        rng = p.stream("forgejust")
        forged = tuple(
            signer.forge(
                Msg(MsgType.ROUND_CHANGE, inst, src, 2), rng
            )
            for src in p.honest
        )
        pp = signer.sign(
            Msg(
                MsgType.PRE_PREPARE,
                inst,
                ADV,
                2,
                "evil",
                justification=forged,
            )
        )
        net.inject_all(pp)

    res = asyncio.run(run_with_adversary(PARAMS, inst, attack))
    decided = assert_agreement(res.decisions)
    assert decided != "evil"
    assert_evidence_only(res.evidence, PARAMS.adversaries)
    assert (
        res.evidence.count(peer=ADV, kind="qbft_forged_justification") >= 1
    )


def test_forged_round_change_prepared_value():
    """Strategy 5: the adversary (silent round-1 leader) injects a
    ROUND-CHANGE claiming `prepared_value="evil"` backed by forged
    PREPARE messages. The forged RC must be rejected — the honest
    round-2 leader proposes its own value, never the planted one."""
    inst = find_instance(4, 1, ADV, prefix="forgerc")

    async def attack(net, signer, p):
        rng = p.stream("forgerc")
        forged = tuple(
            signer.forge(Msg(MsgType.PREPARE, inst, src, 1, "evil"), rng)
            for src in p.honest
        )
        rc = signer.sign(
            Msg(
                MsgType.ROUND_CHANGE,
                inst,
                ADV,
                2,
                prepared_round=1,
                prepared_value="evil",
                justification=forged,
            )
        )
        net.inject_all(rc)

    res = asyncio.run(run_with_adversary(PARAMS, inst, attack))
    decided = assert_agreement(res.decisions)
    assert decided != "evil"
    assert_evidence_only(res.evidence, PARAMS.adversaries)
    assert (
        res.evidence.count(peer=ADV, kind="qbft_forged_justification") >= 1
    )


def test_cross_instance_replay_dropped_and_counted():
    """Strategy 6: a full honest instance's traffic is captured and
    replayed verbatim into a different instance. Every replayed frame
    is dropped and counted; none is re-processed (the second instance
    decides its own value) and no HONEST peer is blamed — the replayed
    frames carry honest source signatures, and the pure harness has no
    channel identity to attribute the relay to."""
    inst_a = find_instance(4, 1, 0, prefix="replayA")
    inst_b = find_instance(4, 1, 1, prefix="replayB")

    res_a = asyncio.run(run_with_adversary(PARAMS, inst_a, None))
    assert_agreement(res_a.decisions)
    captured = list(res_a.net.log)
    assert captured

    async def attack(net, signer, p):
        for m in captured:
            net.inject_all(m)

    res_b = asyncio.run(run_with_adversary(PARAMS, inst_b, attack))
    decided = assert_agreement(res_b.decisions)
    assert decided in {f"value-{i}" for i in PARAMS.honest}
    assert res_b.merged_drops()["replay"] >= len(captured)
    assert_evidence_only(res_b.evidence, PARAMS.adversaries)


def test_round_change_flood_hits_stored_bound():
    """Strategy 7: a ROUND-CHANGE storm for far-future rounds. The
    per-sender stored bound caps what one peer can make the engine
    keep, flood evidence attributes the storm, and a single flooding
    peer can never trigger the f+1 round jump."""
    inst = find_instance(4, 1, 0, prefix="flood")

    async def attack(net, signer, p):
        for rnd in range(2, 120):
            rc = signer.sign(Msg(MsgType.ROUND_CHANGE, inst, ADV, rnd))
            net.inject_all(rc)

    res = asyncio.run(
        run_with_adversary(
            PARAMS, inst, attack, max_stored_per_source=16
        )
    )
    assert assert_agreement(res.decisions) == "value-0"
    assert_evidence_only(res.evidence, PARAMS.adversaries)
    assert res.evidence.count(peer=ADV, kind="qbft_flood") >= 1
    assert res.merged_drops()["flood"] > 0
    # bound held: no engine stored more than the cap from the adversary
    for s in res.stats.values():
        assert s["drops"]["flood"] > 0


def test_framing_resistance_no_evidence_from_forgeries():
    """Strategy 8: the adversary stamps garbage with HONEST source
    indices — conflicting PREPAREs 'from' a victim, a fake PRE-PREPARE
    'from' the real leader. None of it authenticates, so NO evidence
    may be recorded against anyone, and the slots are not squatted (the
    real leader's messages still process)."""
    inst = find_instance(4, 1, 0, prefix="framing")

    async def attack(net, signer, p):
        rng = p.stream("framing")
        victim = 1
        for value in ("x", "y"):
            net.inject_all(
                signer.forge(
                    Msg(MsgType.PREPARE, inst, victim, 1, value), rng
                )
            )
        net.inject_all(
            signer.forge(Msg(MsgType.PRE_PREPARE, inst, 0, 1, "evil"), rng)
        )

    res = asyncio.run(run_with_adversary(PARAMS, inst, attack))
    assert assert_agreement(res.decisions) == "value-0"
    assert res.evidence.snapshot() == {}


def test_malformed_messages_attributed():
    """Strategy 9: validly-signed protocol violations — a PRE-PREPARE
    from a non-leader and an oversized justification — are dropped and
    attributed as malformed."""
    inst = find_instance(4, 1, 0, prefix="malformed")

    async def attack(net, signer, p):
        net.inject_all(
            signer.sign(Msg(MsgType.PRE_PREPARE, inst, ADV, 1, "evil"))
        )
        oversized = tuple(
            signer.sign(Msg(MsgType.PREPARE, inst, ADV, rnd, "x"))
            for rnd in range(1, 10)  # 9 > 2n = 8
        )
        net.inject_all(
            signer.sign(
                Msg(
                    MsgType.ROUND_CHANGE,
                    inst,
                    ADV,
                    2,
                    justification=oversized,
                )
            )
        )

    res = asyncio.run(run_with_adversary(PARAMS, inst, attack))
    assert assert_agreement(res.decisions) == "value-0"
    assert_evidence_only(res.evidence, PARAMS.adversaries)
    assert res.evidence.count(peer=ADV, kind="qbft_malformed") >= 2


# ---------------------------------------------------------------------------
# Partial-signature-plane strategies
# ---------------------------------------------------------------------------


def _att_payload(seed_byte: int):
    from charon_tpu.core.eth2data import AttestationDuty
    from charon_tpu.eth2util.spec import AttestationData, Checkpoint

    data = AttestationData(
        slot=5,
        index=0,
        beacon_block_root=bytes([seed_byte]) * 32,
        source=Checkpoint(0, bytes(32)),
        target=Checkpoint(1, bytes([seed_byte]) * 32),
    )
    return AttestationDuty(
        data=data,
        committee_length=1,
        committee_index=0,
        validator_committee_index=0,
    )


def test_parsigdb_pending_cap_flood():
    """Strategy 10: one share streams partials for fabricated validator
    keys. The per-peer pending cap refuses the overflow with flood
    evidence, while honest shares' thresholds still emit."""
    from charon_tpu.core.eth2data import ParSignedData, SignedData
    from charon_tpu.core.evidence import EvidenceRegistry
    from charon_tpu.core.types import Duty, DutyType, pubkey_from_bytes

    rng = random.Random(f"byz:{SEED}:dbflood")

    def psig(share_idx: int, seed_byte: int) -> ParSignedData:
        return ParSignedData(
            data=SignedData(
                "attestation",
                _att_payload(seed_byte),
                signature=rng.randbytes(96),
            ),
            share_idx=share_idx,
        )

    async def run():
        from charon_tpu.core.parsigdb import ParSigDB

        ev = EvidenceRegistry()
        db = ParSigDB(threshold=3, evidence=ev, max_pending_per_peer=4)
        duty = Duty(5, DutyType.ATTESTER)
        # adversary share 4 floods 12 distinct fabricated pubkeys
        for i in range(12):
            pk = pubkey_from_bytes(b"\xc0" + bytes([i]) + bytes(46))
            await db.store_external(duty, {pk: psig(4, i)})
        assert db.flood_dropped == 12 - 4
        assert ev.count(peer=4, kind="parsig_flood") == 12 - 4
        assert ev.peers() == {4}
        # honest emission unaffected: shares 1..3 on one real key emit
        emitted = []

        async def on_threshold(d, ready):
            emitted.append(ready)

        db.subscribe_threshold(on_threshold)
        pk = pubkey_from_bytes(b"\xd0" + bytes(47))
        honest_sig = rng.randbytes(96)
        for share in (1, 2, 3):
            await db.store_external(
                duty,
                {
                    pk: ParSignedData(
                        data=SignedData(
                            "attestation",
                            _att_payload(99),
                            signature=honest_sig[: 95] + bytes([share]),
                        ),
                        share_idx=share,
                    )
                },
            )
        # same payload root, three distinct shares -> threshold emit
        assert len(emitted) == 1

    asyncio.run(run())


def _silence(node) -> None:
    async def silent_attest(slot, defs):
        return None

    node.vmock.attest = silent_attest


async def _await_attestation(beacon, n_expected: int):
    def done():
        by_slot: dict[int, int] = {}
        for a in beacon.attestations:
            by_slot[a.data.slot] = by_slot.get(a.data.slot, 0) + 1
        return any(c >= n_expected for c in by_slot.values())

    await wait_progress(
        done,
        probe=lambda: len(beacon.attestations),
        what=f"a slot {n_expected} nodes broadcast",
    )


@pytest.mark.slow
def test_simnet_rogue_partial_flood_differential():
    """Strategy 11: the adversary's VC is silent; instead the adversary
    channel injects valid-format forged partial signatures (plausible
    G2 compression flags, garbage field bytes — the chaos plane's
    forged-flood payload). Honest nodes reject every lane, attribute
    the channel, finalize without the adversary — and every device
    verdict matches the python oracle lane-for-lane."""
    from charon_tpu.core.eth2data import ParSignedData, SignedData
    from charon_tpu.core.types import Duty, DutyType
    from charon_tpu.testutil.chaos import forged_signatures
    from charon_tpu.testutil.simnet import build_cluster

    async def run():
        with differential_backend() as diff:
            cluster = build_cluster(
                n=4, t=3, num_validators=1, slot_duration=0.4
            )
            _silence(cluster.nodes[3])
            rng = random.Random(f"byz:{SEED}:rogue")
            sigs = forged_signatures(2, rng)
            pk = cluster.group_pubkeys[0]
            tasks = [
                asyncio.create_task(node.scheduler.run())
                for node in cluster.nodes
            ]
            try:
                # rogue lanes into every honest node, claiming the
                # adversary's own share (channel == claimed: not spoof,
                # but the signatures are forged -> parsig_invalid)
                for node in cluster.nodes[:3]:
                    for sig in sigs:
                        forged = ParSignedData(
                            data=SignedData(
                                "attestation",
                                _att_payload(7),
                                signature=sig,
                            ),
                            share_idx=4,
                        )
                        await node.parsigex.receive(
                            Duty(2, DutyType.ATTESTER),
                            {pk: forged},
                            sender=4,
                        )
                await _await_attestation(cluster.beacon, 4)
            finally:
                for node in cluster.nodes:
                    node.scheduler.stop()
                await asyncio.gather(*tasks, return_exceptions=True)

            for node in cluster.nodes[:3]:
                assert node.parsigex.dropped_invalid == 2
                assert node.evidence.peers() <= {4}
                assert node.evidence.count(peer=4, kind="parsig_invalid") >= 1
            assert_no_mismatches(diff)
            assert diff.lanes_checked > 0

    asyncio.run(run())


@pytest.mark.slow
def test_simnet_double_sign_excluded_from_aggregate():
    """Strategy 12: the adversary's VC double-signs — its real share key
    signs the honest attestation AND a conflicting payload, both
    submitted. Every honest node records the conflict, sigagg excludes
    the adversary's lanes, and all nodes still broadcast the same valid
    group signature (recombined from honest lanes only). Differential:
    zero device-vs-oracle mismatches across the run."""
    from charon_tpu.core.eth2data import SignedData
    from charon_tpu.core.types import pubkey_to_bytes
    from charon_tpu.testutil.simnet import build_cluster

    async def run():
        with differential_backend() as diff:
            cluster = build_cluster(
                n=4, t=3, num_validators=1, slot_duration=0.4
            )
            adv_node = cluster.nodes[3]
            honest_attest = adv_node.vmock.attest

            async def double_sign_attest(slot, defs):
                # the honest duty first (valid lane, honest root) ...
                await honest_attest(slot, defs)
                # ... then a conflicting payload signed with the SAME
                # share key: a slashable double-sign, exchanged to peers
                from charon_tpu.core.eth2data import (
                    Attestation,
                    ParSignedData,
                )
                from charon_tpu.core.types import Duty, DutyType

                for pubkey, d in defs.items():
                    data = await adv_node.vapi.attestation_data(
                        slot, d.committee_index
                    )
                    evil = replace(
                        data, beacon_block_root=b"\xee" * 32
                    )
                    bits = tuple(
                        i == d.validator_committee_index
                        for i in range(d.committee_length)
                    )
                    unsigned = Attestation(
                        aggregation_bits=bits, data=evil
                    )
                    root = SignedData(
                        "attestation", unsigned
                    ).signing_root(
                        cluster.fork,
                        slot // cluster.beacon.slots_per_epoch,
                    )
                    sig = tbls.sign(
                        adv_node.vmock.share_keys[pubkey], root
                    )
                    pset = {
                        pubkey: ParSignedData(
                            data=SignedData(
                                "attestation", unsigned, signature=sig
                            ),
                            share_idx=4,
                        )
                    }
                    await adv_node.parsigdb.store_internal(
                        Duty(slot, DutyType.ATTESTER), pset
                    )

            adv_node.vmock.attest = double_sign_attest
            tasks = [
                asyncio.create_task(node.scheduler.run())
                for node in cluster.nodes
            ]
            try:
                await _await_attestation(cluster.beacon, 4)
            finally:
                for node in cluster.nodes:
                    node.scheduler.stop()
                await asyncio.gather(*tasks, return_exceptions=True)

            # at least one honest node saw both sets and recorded the
            # conflict against the adversary share only
            conflicted = [
                n
                for n in cluster.nodes
                if n.evidence.count(peer=4, kind="parsig_conflict") > 0
            ]
            assert conflicted, "no node detected the double-sign"
            for node in cluster.nodes:
                assert node.evidence.peers() <= {4}
                if node.evidence.excluded_shares():
                    assert node.evidence.excluded_shares() == {4}

            # safety: the broadcast aggregates are all the same valid
            # group signature over the HONEST payload
            by_slot: dict[int, list] = {}
            for a in cluster.beacon.attestations:
                by_slot.setdefault(a.data.slot, []).append(a)
            slot, atts = next(
                (s, v) for s, v in by_slot.items() if len(v) >= 4
            )
            assert len({a.signature for a in atts}) == 1
            assert all(
                a.data.beacon_block_root != b"\xee" * 32 for a in atts
            )
            root = SignedData("attestation", atts[0]).signing_root(
                cluster.fork, slot // cluster.beacon.slots_per_epoch
            )
            tbls.verify(
                pubkey_to_bytes(cluster.group_pubkeys[0]),
                root,
                atts[0].signature,
            )
            assert_no_mismatches(diff)

    asyncio.run(run())


def test_simnet_selective_send_partition():
    """Strategy 13: the adversary sends its (valid) partials to ONE
    honest node only — a selective-send partition. The cluster still
    finalizes everywhere (t honest lanes suffice), and nobody is blamed
    for the silence (selective send is unprovable from one node's view:
    absence of a message is not evidence)."""
    from charon_tpu.testutil.chaos import ChaosConfig
    from charon_tpu.testutil.simnet import build_cluster

    async def run():
        cluster = build_cluster(
            n=4,
            t=3,
            num_validators=1,
            slot_duration=0.4,
            chaos=ChaosConfig(seed=SEED),  # zero-rate: control plane only
        )
        # adversary share 4 reaches only node 1
        cluster.partitioner.block(4, 2)
        cluster.partitioner.block(4, 3)
        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:
            await _await_attestation(cluster.beacon, 4)
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)

        for node in cluster.nodes:
            assert node.evidence.peers() <= {4}

    asyncio.run(run())


# ---------------------------------------------------------------------------
# Differential checker self-test
# ---------------------------------------------------------------------------


def test_differential_tbls_flags_divergence():
    """The conformance checker itself: a deliberately-lying backend must
    produce mismatches; an honest one must not (on valid AND forged
    lanes — agreement on rejection is as load-bearing as agreement on
    acceptance)."""
    from charon_tpu.testutil.chaos import forged_signatures

    py = PythonImpl()
    sk = py.generate_secret_key()
    pk = py.secret_to_public_key(sk)
    sig = py.sign(sk, b"m" * 32)
    forged = forged_signatures(1, random.Random(SEED))[0]

    honest = DifferentialTbls(inner=py, oracle=PythonImpl())
    assert honest.verify_batch(
        [(pk, b"m" * 32, sig), (pk, b"m" * 32, forged)]
    ) == [True, False]
    assert honest.mismatches == []
    assert honest.lanes_checked == 2

    class Liar(PythonImpl):
        def verify(self, pubkey, data, s):  # accepts everything
            return None

    lying = DifferentialTbls(inner=Liar(), oracle=PythonImpl())
    lying.verify_batch([(pk, b"m" * 32, forged)])
    assert len(lying.mismatches) == 1
    with pytest.raises(AssertionError):
        assert_no_mismatches(lying)
