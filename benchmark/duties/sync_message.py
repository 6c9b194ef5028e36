"""The sync-committee message duty, as a mix's `duties` names it: the first
`sync_committee_members` validators of the plan's seeded order sit in the
committee and sign, in EVERY slot, the one head block root of that slot (the
root the slot's attesters vote for, from the seed, the same on every
operator's beacon), the trigger at 1/3 slot beside the attester's. The whole
wave shares one signing root. Contributions (2/3 slot, selection proofs) are
not driven: the harness's VC sends no selections yet (a duty that validator
clients start is a kind of its own since PR 43: README.md, "Adding things"),
and the node's contribution duty waits out its deadline as its aggregator
duty does. README.md says what the harness asks of a kind's module. Added in
PR 37 and rehearsed on the CPU (tests/rehearse_sync.py); since PR 39 the cell
`dv-3of4-1k-sync.attest-sync` runs it on the chip."""

from __future__ import annotations

import time

from benchmark import reference_sync, signer

NAME = "sync_message"
DUTY_TYPE = "SYNC_MESSAGE"  # the member of core.types.DutyType
OFFSET = 1.0 / 3.0  # the trigger's place in the slot
SUBMIT = "submit_sync_message"  # where the node's beacon gets the aggregate
DATA_CHECK = "attestation_data_differ"  # check.py: where a record's data counts
VC_SPANS = ("vc_head_root", "vc_sign", "http_submit")


# -- 1. the schedule ----------------------------------------------------------


def members(plan, slot: int) -> list[int]:
    """The committee, in the seeded order: the same in every slot."""
    k = int(plan.sizes["sync_committee_members"])
    return sorted((v for v, r in enumerate(plan.rank) if r < k), key=plan.rank.__getitem__)


# -- 5. the shapes ------------------------------------------------------------


def shapes(plan) -> set[str]:
    return plan.wave_shapes(len(members(plan, 0)))


# -- 2. the beacon's side -----------------------------------------------------


def block_root(plan, slot: int) -> bytes:
    return plan.block_root("block", slot)


def beacon(scene) -> dict:
    """What every operator's BeaconMock answers for this kind: the
    scheduler's `sync_duties` (a member's position in the committee is its
    place in the seeded order), the fetcher's `sync_committee_block_root`."""
    plan, cluster = scene.plan, scene.cluster
    committee = members(plan, 0)

    async def sync_duties(self, epoch, vals):
        return [
            dict(pubkey=cluster.pubkeys[vidx], validator_index=vals[cluster.pubkeys[vidx]],
                 sync_committee_indices=[position])
            for position, vidx in enumerate(committee)
            if cluster.pubkeys[vidx] in vals
        ]

    async def sync_committee_block_root(self, slot):
        return block_root(plan, slot)

    return {"sync_duties": sync_duties, "sync_committee_block_root": sync_committee_block_root}


def submitted(scene, msg):
    """The aggregate the node's beacon got -> (slot, validator, signature,
    raw fields) of its record; a validator's index is its place in the lock."""
    return (msg.slot, msg.validator_index, msg.signature,
            (msg.slot, msg.beacon_block_root, msg.validator_index))


# -- 3. the signer's side -----------------------------------------------------


def signing_root(scene, slot: int, root: bytes) -> bytes:
    from charon_tpu.core.eth2data import SignedData, SyncCommitteeMessage

    return scene.memo.once(
        ("sync_message_root", slot, root),  # whoever signs: the validator is not in the root
        lambda: SignedData("sync_message", SyncCommitteeMessage(slot, root, 0)).signing_root(
            scene.fork, slot // scene.plan.slots_per_epoch))


def sign_messages(scene, share_keys, slot: int, roots_by_pubkey: dict, signed_roots=None):
    """pubkey -> block root => {pubkey: SyncCommitteeMessage} signed with
    the share keys by the harness's signer; `signed_roots` collects
    pubkey -> signing root."""
    from charon_tpu.core.eth2data import SyncCommitteeMessage

    out = {}
    for pubkey, root in roots_by_pubkey.items():
        to_sign = signing_root(scene, slot, root)
        if signed_roots is not None:
            signed_roots[pubkey] = to_sign
        out[pubkey] = SyncCommitteeMessage(
            slot, root, scene.cluster.validators[pubkey],
            signer.sign(share_keys[pubkey], to_sign))
    return out


def sign(scene, share_keys, duty, unsigned_set) -> dict:
    """One operator's partials of a decided set: pubkey -> SignedData."""
    from charon_tpu.core.eth2data import SignedData

    msgs = sign_messages(scene, share_keys, duty.slot,
                         {pk: d.beacon_block_root for pk, d in unsigned_set.items()})
    return {pk: SignedData("sync_message", m, m.signature) for pk, m in msgs.items()}


# -- 4. the VC's round --------------------------------------------------------


async def vc_round(server, duty, defs) -> list:
    """The node's validator client: the cluster-agreed head root from the
    node's ValidatorAPI over HTTP, one message a member signed with operator
    1's shares, all submitted in one request."""
    t0 = time.time()
    root = await server.client.head_root(duty.slot)
    t1 = time.time()
    signed_roots: dict = {}
    msgs = sign_messages(server.scene, server.cluster.share_keys[0], duty.slot,
                         dict.fromkeys(defs, root), signed_roots)
    for pk, to_sign in signed_roots.items():
        rec = server.record(NAME, duty.slot, server.cluster.validators[pk])
        if rec is not None:
            rec.root = to_sign
    t2 = time.time()
    await server.client.submit_sync_messages(list(msgs.values()))
    t3 = time.time()
    return [("vc_head_root", t0, t1), ("vc_sign", t1, t2), ("http_submit", t2, t3)]


# -- 6. the expected answer ---------------------------------------------------


def expected(plan, record, chain: tuple[bytes, bytes]) -> tuple:
    """(raw fields, signing root) of a record by the plain reference."""
    root = block_root(plan, record.slot)
    return ((record.slot, root, record.vidx),
            reference_sync.sync_message_signing_root(root, *chain))
