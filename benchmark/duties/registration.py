"""The builder registration duty, as a mix's `duties` names it: every
operator's validator client re-sends its validators' registrations (upstream
`POST /eth/v1/validator/register_validator`, duty `DutyBuilderRegistration`;
builder-specs `ValidatorRegistrationV1`), signed with its share; the partials
cross ParSigEx, the node recombines and sends the group-signed registration
to its beacon. No scheduler emits the duty and no consensus runs on it: the
VC STARTS it (`STARTS = "vc"`: README.md, "Adding things"), at the start of a
slot, four seconds before the attester trigger.

The schedule, from two sizes of the configuration: in a slot `s` with
`s % registration_every_slots == 0` the VCs register the
`registrations_per_batch` validators of ranks `[k x B, (k + 1) x B)` mod
`validators` of the plan's seeded order, `k = s // E`, in ONE request an
operator; in other slots none. A registration, from the seed: 20 seeded
bytes of fee recipient a validator, upstream's default gas limit, the first
whole second of the slot as its timestamp (a VC that signs afresh; the run's
genesis is no whole second, so the slot's start is rounded UP into the
slot), the validator's group key. By upstream's rule the duty's slot is the
slot of the timestamp: the slot the request was sent in.

The node's Broadcaster keeps every registration it sent and its recaster
sends them all again at the start of every epoch (upstream's Recaster, by
design). `submitted` tells that from a second broadcast by CONTENT: a later
delivery whose fields and signature are byte for byte the first one's is the
recaster's and is no broadcast of the duty; any other reaches the record, as
a duplicate.

Added in PR 43 with no cell: rehearsed on the CPU only
(tests/rehearse_register.py, with the patches the parent program needs)."""

from __future__ import annotations

import asyncio
import functools
import hashlib
import math
import time

from benchmark import reference_registration, signer

NAME = "registration"
STARTS = "vc"  # validator clients send it; nothing schedules or decides it
DUTY_TYPE = "BUILDER_REGISTRATION"  # the member of core.types.DutyType
OFFSET = 0.0  # the request's place in the slot
SUBMIT = "submit_registration"  # where the node's beacon gets the aggregate
DATA_CHECK = "attestation_data_differ"  # check.py: where a record's data counts
VC_SPANS = ("vc_registrations", "vc_sign", "http_submit")
GAS_LIMIT = 30_000_000  # upstream's default (eth2util/registration)


# -- 1. the schedule ----------------------------------------------------------


@functools.lru_cache(maxsize=4)
def _order(rank: tuple) -> tuple:
    """rank -> validator: the plan's seeded order, read the other way."""
    return tuple(sorted(range(len(rank)), key=rank.__getitem__))


def sizes(plan) -> tuple[int, int]:
    batch = int(plan.sizes["registrations_per_batch"])
    every = int(plan.sizes["registration_every_slots"])
    if not 1 <= batch <= plan.validators or every < 1:
        raise ValueError(f"registrations_per_batch {batch} of {plan.validators} validators, "
                         f"registration_every_slots {every}")
    return batch, every


def members(plan, slot: int) -> list[int]:
    """The validators whose registrations the VCs send in `slot`."""
    batch, every = sizes(plan)
    if slot % every:
        return []
    order, first = _order(plan.rank), (slot // every) * batch
    return [order[r % plan.validators] for r in range(first, first + batch)]


def duty(plan, slot: int):
    """The Duty the partials travel under: the slot of the timestamp."""
    from charon_tpu.core.types import Duty, DutyType

    return Duty(slot, DutyType.BUILDER_REGISTRATION)


# -- 5. the shapes ------------------------------------------------------------


def shapes(plan) -> set[str]:
    """One request is one set an operator: a wave of B duties."""
    return plan.wave_shapes(sizes(plan)[0])


# -- 2. the beacon's side -----------------------------------------------------


def fee_recipient(seed: int, vidx: int) -> bytes:
    return hashlib.sha256(f"fee/{seed}/{vidx}".encode()).digest()[:20]


def timestamp(slot_start: float) -> int:
    """The first whole second of the slot that starts at `slot_start`."""
    return math.ceil(slot_start)


def beacon(scene) -> dict:
    """Nothing to answer: no scheduler asks a beacon for this duty."""
    return {}


def unsigned(scene, slot: int) -> dict:
    """pubkey -> the ValidatorRegistration every operator's VC signs in
    `slot`, made once for all of them."""
    from charon_tpu.eth2util.registration import ValidatorRegistration

    plan, cluster = scene.plan, scene.cluster
    at = timestamp(scene.genesis + slot * plan.slot_duration)
    return scene.memo.once(("registrations", slot), lambda: {
        cluster.pubkeys[vidx]: ValidatorRegistration(
            fee_recipient(plan.seed, vidx), GAS_LIMIT, at,
            bytes.fromhex(cluster.pubkeys[vidx][2:]))
        for vidx in members(plan, slot)})


def submitted(scene, reg, signature):
    """The aggregate the node's beacon got -> (slot, validator, signature,
    raw fields) of its record: the slot is the timestamp's, whenever it
    comes. None where the key is no validator of the cluster, and where the
    delivery is, byte for byte, the first one the beacon got of that slot
    and validator: the recaster's re-send. One that differs in a field or in
    its signature is returned, and counts as a second broadcast."""
    vidx = scene.cluster.validators.get("0x" + reg.pubkey.hex())
    if vidx is None:
        return None
    slot = math.floor((reg.timestamp - scene.genesis) / scene.plan.slot_duration + 1e-9)
    mine = (signature, (reg.fee_recipient, reg.gas_limit, reg.timestamp, reg.pubkey))
    first = scene.memo.once("registrations_delivered", dict).setdefault((slot, vidx), mine)
    if first is not mine and first == mine:
        return None
    return (slot, vidx, *mine)


# -- 3. the signer's side -----------------------------------------------------


def signing_root(scene, reg) -> bytes:
    from charon_tpu.core.eth2data import SignedData

    return scene.memo.once(
        ("registration_root", reg.pubkey, reg.timestamp),
        lambda: SignedData("registration", reg).signing_root(scene.fork, 0))


def sign_registrations(scene, share_keys, regs: dict, roots=None) -> dict:
    """pubkey -> ValidatorRegistration => pubkey -> signature by the share
    keys (the harness's signer: the GIL is released while it signs);
    `roots` collects pubkey -> signing root."""
    out = {}
    for pubkey, reg in regs.items():
        root = signing_root(scene, reg)
        if roots is not None:
            roots[pubkey] = root
        out[pubkey] = signer.sign(share_keys[pubkey], root)
    return out


def sign(scene, share_keys, duty, unsigned_set) -> dict:
    """One operator's partials of the slot's set: pubkey -> SignedData."""
    from charon_tpu.core.eth2data import SignedData

    sigs = sign_registrations(scene, share_keys, unsigned_set)
    return {pk: SignedData("registration", unsigned_set[pk], sig) for pk, sig in sigs.items()}


# -- 4. the VC's round --------------------------------------------------------


async def vc_round(server, duty, defs) -> list:
    """The node's validator client: the slot's registrations (`defs`: the
    objects every VC signs) signed with operator 1's shares ON A THREAD — a
    validator client is another process, and hundreds of signatures may not
    hold the node's event loop — and sent in ONE request."""
    t0 = time.time()
    regs = dict(defs)
    t1 = time.time()
    roots: dict = {}
    sigs = await asyncio.to_thread(
        sign_registrations, server.scene, server.cluster.share_keys[0], regs, roots)
    for pk, root in roots.items():
        rec = server.record(NAME, duty.slot, server.cluster.validators[pk])
        if rec is not None:
            rec.root = root
    t2 = time.time()
    await server.client.register_validators([(regs[pk], sig) for pk, sig in sigs.items()])
    t3 = time.time()
    return [("vc_registrations", t0, t1), ("vc_sign", t1, t2), ("http_submit", t2, t3)]


# -- 6. the expected answer ---------------------------------------------------


def expected(plan, record, chain: tuple[bytes, bytes]) -> tuple:
    """(raw fields, signing root) of a record by the plain reference. The
    timestamp is the first whole second from the instant the record was due
    (OFFSET 0: the slot's start); `chain[0]` is the lock's fork version,
    which in the benchmark's clusters is the genesis fork version the builder
    domain asks for (reference_registration.py says so too)."""
    f = (fee_recipient(plan.seed, record.vidx), GAS_LIMIT, timestamp(record.due),
         bytes.fromhex(record.pubkey[2:]))
    return f, reference_registration.registration_signing_root(f, chain[0])
