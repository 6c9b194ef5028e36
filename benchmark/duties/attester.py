"""The attester duty, as a mix's `duties` names it: every validator attests
ONCE an epoch, in the slot the plan's seeded order gives it (31 or 32 of
1,000 a slot), every attester in a committee of its own (configuration:
committees_per_slot), the trigger at 1/3 slot. README.md, "Adding things",
says what the harness asks of a kind's module; this is everything it did
for the attester before a kind was a file (PR 37), behaviour for behaviour."""

from __future__ import annotations

import time

from benchmark import reference, signer

NAME = "attester"
DUTY_TYPE = "ATTESTER"  # the member of core.types.DutyType
OFFSET = 1.0 / 3.0  # the trigger's place in the slot
SUBMIT = "submit_attestation"  # where the node's beacon gets the aggregate
DATA_CHECK = "attestation_data_differ"  # check.py: where a record's data counts
VC_SPANS = ("vc_attestation_data", "vc_sign", "http_submit")


# -- 1. the schedule ----------------------------------------------------------


def members(plan, slot: int) -> list[int]:
    """Validator indices attesting in `slot`, in committee order."""
    return plan.members(slot)


# -- 5. the shapes ------------------------------------------------------------


def shapes(plan) -> set[str]:
    return set().union(*(plan.wave_shapes(plan.duties_in(pos))
                         for pos in range(plan.slots_per_epoch)))


# -- 2. the beacon's side -----------------------------------------------------


def fields(plan, slot: int, committee_index: int) -> tuple:
    """The raw fields of the slot's AttestationData, the same on every
    operator's beacon: (slot, index, beacon block root, source epoch,
    source root, target epoch, target root). The program's objects
    and the plain reference's signing root are both made from these."""
    epoch = slot // plan.slots_per_epoch
    return (slot, committee_index, plan.block_root("block", slot),
            max(0, epoch - 1), plan.block_root("cp", epoch - 1),
            epoch, plan.block_root("cp", epoch))


def slot_data(scene, slot: int, committee_index: int):
    """(AttestationData, its hash tree root), made once for all operators."""
    from charon_tpu.core.eth2data import AttestationData, Checkpoint

    def make():
        _s, _i, block, s_epoch, s_root, t_epoch, t_root = fields(
            scene.plan, slot, committee_index)
        data = AttestationData(
            slot=slot,
            index=committee_index,
            beacon_block_root=block,
            source=Checkpoint(s_epoch, s_root),
            target=Checkpoint(t_epoch, t_root),
        )
        return data, data.hash_tree_root()

    return scene.memo.once(("attestation_data", slot, committee_index), make)


def beacon(scene) -> dict:
    """What every operator's BeaconMock answers for this kind: the
    scheduler's `attester_duties`, the fetcher's `attestation_data`."""
    plan, cluster = scene.plan, scene.cluster
    spe = plan.slots_per_epoch
    by_pos = {p: plan.members(p) for p in range(spe)}

    async def attester_duties(self, epoch, vals):
        return [
            dict(
                slot=epoch * spe + pos,
                pubkey=cluster.pubkeys[vidx],
                validator_index=vals[cluster.pubkeys[vidx]],
                committee_index=ci,
                committee_length=1,
                committees_at_slot=len(members),
                validator_committee_index=0,
            )
            for pos, members in sorted(by_pos.items())
            for ci, vidx in enumerate(members)
            if cluster.pubkeys[vidx] in vals
        ]

    async def attestation_data(self, slot, committee_index):
        data, root = slot_data(scene, slot, committee_index)
        self._att_data_by_root[root] = data
        return data

    return {"attester_duties": attester_duties, "attestation_data": attestation_data}


def submitted(scene, att):
    """The aggregate the node's beacon got -> (slot, validator, signature,
    raw fields) of its record, or None where it is no duty of the plan."""
    slot = att.data.slot
    members = scene.plan.members(slot)
    if not 0 <= att.data.index < len(members):
        return None
    d = att.data
    return (slot, members[d.index], att.signature,
            (d.slot, d.index, d.beacon_block_root, d.source.epoch,
             d.source.root, d.target.epoch, d.target.root))


# -- 3. the signer's side -----------------------------------------------------


def signing_root(scene, data, bits) -> bytes:
    from charon_tpu.core.eth2data import Attestation, SignedData

    return scene.memo.once(
        ("attestation_root", data.slot, data.index, bits),
        lambda: SignedData("attestation", Attestation(bits, data)).signing_root(
            scene.fork, data.slot // scene.plan.slots_per_epoch))


def sign_attestations(scene, share_keys, duties, roots=None):
    """duties: pubkey -> (AttestationData, committee_length, position)
    -> {pubkey: Attestation} signed with the share keys by the harness's
    signer (C++ through ctypes: the GIL is released while it signs);
    `roots` collects pubkey -> signing root."""
    from charon_tpu.core.eth2data import Attestation

    out = {}
    for pubkey, (data, length, pos) in duties.items():
        bits = tuple(i == pos for i in range(length))
        root = signing_root(scene, data, bits)
        if roots is not None:
            roots[pubkey] = root
        out[pubkey] = Attestation(bits, data, signer.sign(share_keys[pubkey], root))
    return out


def sign(scene, share_keys, duty, unsigned_set) -> dict:
    """One operator's partials of a decided set: pubkey -> SignedData."""
    from charon_tpu.core.eth2data import SignedData

    atts = sign_attestations(scene, share_keys, {
        pk: (d.data, d.committee_length, d.validator_committee_index)
        for pk, d in unsigned_set.items()})
    return {pk: SignedData("attestation", att, att.signature) for pk, att in atts.items()}


# -- 4. the VC's round --------------------------------------------------------


async def vc_round(server, duty, defs) -> list:
    """The node's validator client: attestation data from the node's
    ValidatorAPI over HTTP, signed with operator 1's shares, submitted."""
    t0 = time.time()
    duties, data_by_committee = {}, {}
    for pk, d in defs.items():
        if d.committee_index not in data_by_committee:
            data_by_committee[d.committee_index] = (
                await server.client.attestation_data(duty.slot, d.committee_index)
            )
        duties[pk] = (
            data_by_committee[d.committee_index],
            d.committee_length,
            d.validator_committee_index,
        )
    t1 = time.time()
    roots: dict = {}
    atts = sign_attestations(server.scene, server.cluster.share_keys[0], duties, roots)
    for pk, root in roots.items():
        rec = server.record(NAME, duty.slot, server.cluster.validators[pk])
        if rec is not None:
            rec.root = root
    t2 = time.time()
    await server.client.submit_attestations(list(atts.values()))
    t3 = time.time()
    return [("vc_attestation_data", t0, t1), ("vc_sign", t1, t2), ("http_submit", t2, t3)]


# -- 6. the expected answer ---------------------------------------------------


def expected(plan, record, chain: tuple[bytes, bytes]) -> tuple:
    """(raw fields, signing root) of a record by the plain reference."""
    f = fields(plan, record.slot, plan.members(record.slot).index(record.vidx))
    return f, reference.attestation_signing_root(f, *chain)
