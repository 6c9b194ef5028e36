"""A validator client's builder registrations on the served path (ISSUE 44,
`dv-3of4-1k-reg.attest-register`): a `register_validator` request is ONE
submission (one `vapi.submit`, one pubshare batch, one set a duty slot under
the wave key the peers' sets carry), each registration filed under the slot
of its timestamp — never slot 0 by default; the node's group-signed
registration is byte-equal to the plain reference's signature on the plain
reference's root; the coalescer's existing rules on a wave the VC starts
four seconds before the attester's; the deployment's files; the rehearsal
of the whole control flow on the CPU with NO patch
(benchmark/tests/rehearse_register.py --unpatched: the program's own path
since this PR)."""

from __future__ import annotations

import asyncio
import functools
import hashlib
import json
import math
import subprocess
import sys
import types
from pathlib import Path

import aiohttp
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import manifest as M  # noqa: E402
from benchmark import reference  # noqa: E402
from benchmark import reference_registration as RR  # noqa: E402
from benchmark import traffic as T  # noqa: E402
from charon_tpu import tbls  # noqa: E402
from charon_tpu.app import tracer  # noqa: E402
from charon_tpu.core import eth2data as d  # noqa: E402
from charon_tpu.core.deadline import SlotClock  # noqa: E402
from charon_tpu.core.parsigdb import ParSigDB  # noqa: E402
from charon_tpu.core.parsigex import WaveRoster, WaveSet  # noqa: E402
from charon_tpu.core.sigagg import SigAgg  # noqa: E402
from charon_tpu.core.types import Duty, DutyType, pubkey_from_bytes  # noqa: E402
from charon_tpu.core.validatorapi import PreGenesisError, ValidatorAPI, VapiError  # noqa: E402
from charon_tpu.core.vapi_http import VapiRouter  # noqa: E402
from charon_tpu.eth2util.registration import ValidatorRegistration  # noqa: E402
from charon_tpu.tbls.native_impl import NativeImpl  # noqa: E402
from tests import test_cryptoplane as CP  # noqa: E402
from tests.test_cryptoplane import FORK, clock  # noqa: E402,F401 — the still clock
from tests.test_two_kinds import RecordingPlane  # noqa: E402

CELL = "dv-3of4-1k-reg.attest-register"
KIND = "builder_registration"
GENESIS, SLOT_S = 1_790_000_000.37, 12.0  # no whole second, as a run's genesis
SLOT, VALIDATORS = 41, 5
PATH = "/eth/v1/validator/register_validator"


def _timestamp(slot: int) -> int:
    """The first whole second of `slot` (duties/registration.timestamp)."""
    return math.ceil(GENESIS + slot * SLOT_S)


@pytest.fixture
def vc(request):
    """A node's ValidatorAPI over HTTP with operator 1's shares of
    VALIDATORS validators — secrets and Shamir shares the plain
    reference's own, from a seed — its ParSigDB and SigAgg behind it, and
    what reached the plane, the database and the broadcaster."""
    seed = getattr(request, "param", 1)
    impl = NativeImpl()
    tbls.set_implementation(impl)
    secrets, shares, pubkeys = [], [], []
    for v in range(VALIDATORS):
        secret = reference.seeded_scalar("test-register", seed, v).to_bytes(32, "big")
        secrets.append(secret)
        shares.append(reference.threshold_split(secret, 4, 3, "test-split", seed, v))
        pubkeys.append(pubkey_from_bytes(impl.secret_to_public_key(secret)))
    plane, ring = RecordingPlane(), tracer.Tracer(capacity=128)
    vapi = ValidatorAPI(
        share_idx=1,
        pubshares={pk: impl.secret_to_public_key(shares[v][1]) for v, pk in enumerate(pubkeys)},
        fork=FORK, slots_per_epoch=32, plane=plane, tracer=ring,
        roster=WaveRoster(range(1, 5)), clock=SlotClock(GENESIS, SLOT_S))
    db, agg = ParSigDB(3), SigAgg(threshold=3, fork=FORK)
    stored, aggregated = [], []

    async def store(duty, signed_set):
        stored.append((duty, signed_set))
        await db.store_internal(duty, signed_set)

    async def broadcast(duty, data_set):
        aggregated.append((duty, data_set))

    vapi.subscribe(store)
    db.subscribe_threshold(agg.aggregate)
    agg.subscribe(broadcast)
    router = VapiRouter(vapi, validators={pk: 100 + v for v, pk in enumerate(pubkeys)},
                        genesis_time=GENESIS, slot_duration=SLOT_S)
    taken = []
    router.on_registrations = lambda result, count: taken.append((result, count))

    def registration(v, slot=SLOT, timestamp=None):
        return ValidatorRegistration(
            fee_recipient=hashlib.sha256(f"fee/{seed}/{v}".encode()).digest()[:20],
            gas_limit=30_000_000,
            timestamp=_timestamp(slot) if timestamp is None else timestamp,
            pubkey=bytes.fromhex(pubkeys[v][2:]))

    def partial(v, reg, share_idx=1):
        root = d.SignedData("registration", reg).signing_root(FORK, 0)
        return impl.sign(shares[v][share_idx], root)

    def entry(v, reg=None, signature=None):
        reg = reg or registration(v)
        return {"message": {"fee_recipient": "0x" + reg.fee_recipient.hex(),
                            "gas_limit": str(reg.gas_limit), "timestamp": str(reg.timestamp),
                            "pubkey": "0x" + reg.pubkey.hex()},
                "signature": "0x" + (signature or partial(v, reg)).hex()}

    async def post(body):
        port = await router.start()
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.post(f"http://127.0.0.1:{port}{PATH}", json=body) as resp:
                    return resp.status, await resp.text()
        finally:
            await router.stop()

    async def peer_set(share_idx, members, slot=SLOT):
        """A peer's set of the same registrations, as ParSigEx stores it."""
        await db.store_external(Duty(slot, DutyType.BUILDER_REGISTRATION), {
            pubkeys[v]: d.ParSignedData(
                d.SignedData("registration", registration(v, slot),
                             partial(v, registration(v, slot), share_idx)), share_idx)
            for v in members})

    yield types.SimpleNamespace(
        seed=seed, secrets=secrets, pubkeys=pubkeys, vapi=vapi, router=router, plane=plane,
        ring=ring, stored=stored, aggregated=aggregated, taken=taken,
        registration=registration, partial=partial, entry=entry, post=post, peer_set=peer_set)
    from charon_tpu.tbls.python_impl import PythonImpl

    tbls.set_implementation(PythonImpl())


def _submits(ring):
    return [s for s in ring.spans if s.name == "vapi.submit"]


def _reference_root(reg) -> bytes:
    fields = (reg.fee_recipient, reg.gas_limit, reg.timestamp, reg.pubkey)
    return RR.registration_signing_root(fields, bytes(FORK.genesis_fork_version))


# -- a request is one set, under the slot of its timestamp -----------------------


@pytest.mark.parametrize("k", [VALIDATORS, 1])
def test_a_request_of_k_registrations_is_one_submit(vc, k):
    """ONE `vapi.submit` span (`duty_type` builder_registration, `count` the
    request's size), ONE verify job of k lanes on k distinct roots under the
    key a peer's set of the same registrations carries, ONE set handed on,
    filed under the slot of the timestamp."""
    status, text = asyncio.run(vc.post([vc.entry(v) for v in range(k)]))
    assert status == 200, text
    (span,) = _submits(vc.ring)
    assert (span.attrs["duty_type"], span.attrs["count"], span.attrs["rejected"]) == (KIND, k, 0)
    assert span.attrs["duty"] == f"{SLOT}/{KIND}"
    ((items, wave),) = vc.plane.calls
    duty = Duty(SLOT, DutyType.BUILDER_REGISTRATION)
    pubkeys = frozenset(vc.pubkeys[:k])
    assert wave == (((duty, pubkeys), WaveSet(1, frozenset({1, 2, 3, 4}), 4)),)
    # every lane its own signing root, each the plain reference's
    roots = [root for _pk, root, _sig in items]
    assert len(set(roots)) == k
    assert roots == [_reference_root(vc.registration(v)) for v in range(k)]
    ((stored_duty, signed_set),) = vc.stored
    assert stored_duty == duty and set(signed_set) == pubkeys
    assert {p.share_idx for p in signed_set.values()} == {1}
    assert vc.router.registrations_taken == {"accepted": k, "rejected": 0, "pre_genesis": 0}
    assert vc.taken == [("accepted", k)]


def test_a_request_that_spans_two_slots_is_one_submission_and_a_set_a_slot(vc):
    """A VC that re-sends what it signed a slot ago beside what it signs
    now: one span, one pubshare batch, and a set under each timestamp's
    slot — each with the wave key that slot's peers carry."""
    body = [vc.entry(v, vc.registration(v, SLOT - (v % 2))) for v in range(4)]
    status, text = asyncio.run(vc.post(body))
    assert status == 200, text
    (span,) = _submits(vc.ring)
    assert span.attrs["count"] == 4
    ((items, wave),) = vc.plane.calls
    assert len(items) == 4
    assert [(key[0].slot, sorted(key[1])) for key, _hint in wave] == [
        (SLOT, sorted([vc.pubkeys[0], vc.pubkeys[2]])),
        (SLOT - 1, sorted([vc.pubkeys[1], vc.pubkeys[3]]))]
    assert sorted(duty.slot for duty, _set in vc.stored) == [SLOT - 1, SLOT]
    assert all(duty.type == DutyType.BUILDER_REGISTRATION for duty, _set in vc.stored)


def test_one_bad_partial_refuses_the_request_whole(vc):
    body = [vc.entry(v) for v in range(VALIDATORS)]
    reg = vc.registration(3)
    body[3] = vc.entry(3, reg, vc.partial(0, reg))  # another validator's share
    status, text = asyncio.run(vc.post(body))
    assert status == 400 and "pubshare verification" in text
    (span,) = _submits(vc.ring)
    assert (span.attrs["count"], span.attrs["rejected"], span.status) == (VALIDATORS, 1, "error")
    assert len(vc.plane.calls) == 1 and vc.stored == []
    assert vc.router.registrations_taken == {
        "accepted": 0, "rejected": VALIDATORS, "pre_genesis": 0}


@pytest.mark.parametrize("timestamp", [int(GENESIS), 0, 1_606_824_023],
                         ids=["the-second-genesis-lies-in", "zero", "years-before"])
def test_a_timestamp_before_genesis_is_refused_and_never_filed_under_slot_zero(vc, timestamp):
    """Upstream fails such a request ("registration timestamp before
    genesis"); filed under slot 0 it would meet no peer's partial. 400, and
    nothing of the request — its well-timed registrations neither —
    reaches the plane or the database."""
    body = [vc.entry(0), vc.entry(1, vc.registration(1, timestamp=timestamp))]
    status, text = asyncio.run(vc.post(body))
    assert status == 400 and "before genesis" in text
    assert vc.plane.calls == [] and vc.stored == [] and _submits(vc.ring) == []
    assert vc.router.registrations_taken == {"accepted": 0, "rejected": 0, "pre_genesis": 2}
    assert vc.taken == [("pre_genesis", 2)]
    reg = vc.registration(1, timestamp=timestamp)
    with pytest.raises(PreGenesisError):
        asyncio.run(vc.vapi.submit_registration(vc.pubkeys[1], reg, vc.partial(1, reg)))
    assert vc.stored == []


def test_the_first_second_of_the_genesis_slot_is_slot_zero_because_it_says_so(vc):
    reg = vc.registration(0, slot=0)
    assert reg.timestamp == int(GENESIS) + 1
    asyncio.run(vc.vapi.submit_registration(vc.pubkeys[0], reg, vc.partial(0, reg)))
    ((duty, _set),) = vc.stored
    assert duty == Duty(0, DutyType.BUILDER_REGISTRATION)


@pytest.mark.parametrize("slot,filed", [(None, SLOT), (7, 7), (0, 0)],
                         ids=["by-default-the-timestamps", "the-slot-it-is-told", "zero-if-told"])
def test_the_single_registration_call_is_a_request_of_one(vc, slot, filed):
    reg = vc.registration(2)
    kwargs = {} if slot is None else {"slot": slot}
    asyncio.run(vc.vapi.submit_registration(vc.pubkeys[2], reg, vc.partial(2, reg), **kwargs))
    (span,) = _submits(vc.ring)
    assert (span.attrs["duty_type"], span.attrs["count"]) == (KIND, 1)
    ((duty, signed_set),) = vc.stored
    assert duty == Duty(filed, DutyType.BUILDER_REGISTRATION) and list(signed_set) == [vc.pubkeys[2]]
    ((_items, wave),) = vc.plane.calls
    assert [key for key, _hint in wave] == [(duty, frozenset({vc.pubkeys[2]}))]


def test_a_clockless_api_takes_its_routers_clock_and_none_names_no_slot():
    vapi = ValidatorAPI(share_idx=1, pubshares={}, fork=FORK)
    reg = ValidatorRegistration(bytes(20), 1, _timestamp(SLOT), bytes(48))
    with pytest.raises(VapiError, match="slot clock"):
        vapi.registration_slot(reg)
    router = VapiRouter(vapi, genesis_time=GENESIS, slot_duration=SLOT_S)
    assert vapi.clock is router.clock and vapi.registration_slot(reg) == SLOT
    own = SlotClock(GENESIS + SLOT_S, SLOT_S)
    kept = ValidatorAPI(share_idx=1, pubshares={}, fork=FORK, clock=own)
    VapiRouter(kept, genesis_time=GENESIS, slot_duration=SLOT_S)
    assert kept.clock is own and kept.registration_slot(reg) == SLOT - 1


def test_an_empty_request_is_accepted_and_submits_nothing(vc):
    status, _text = asyncio.run(vc.post([]))
    assert status == 200 and vc.plane.calls == [] and _submits(vc.ring) == []
    assert vc.taken == []


# -- the VC's partials meet the peers', and the aggregate is the reference's -----


@pytest.mark.parametrize("vc", [1, 4300000013, 2**31 + 12345], indirect=True)
def test_the_group_signed_registration_is_the_references_signature_on_its_root(vc):
    """The node's own set (through the router) and two peers' sets under
    the duty of the timestamp's slot reach t = 3: what SigAgg hands the
    broadcaster is, byte for byte, the plain reference's signature by the
    group secret on the plain reference's signing root."""
    members = [0, 3]

    async def main():
        status, text = await vc.post([vc.entry(v) for v in members])
        assert status == 200, text
        assert vc.aggregated == []  # one partial each: below t
        await vc.peer_set(2, members)
        assert vc.aggregated == []
        await vc.peer_set(4, members)

    asyncio.run(main())
    ((duty, data_set),) = vc.aggregated
    assert duty == Duty(SLOT, DutyType.BUILDER_REGISTRATION)
    assert sorted(data_set) == sorted(vc.pubkeys[v] for v in members)
    for v in members:
        signed = data_set[vc.pubkeys[v]]
        root = _reference_root(vc.registration(v))
        assert signed.signing_root(FORK, 0) == root
        assert signed.signature == reference.sign(vc.secrets[v], root)
        assert signed.payload == vc.registration(v)


def test_a_set_filed_under_slot_zero_never_meets_the_peers(vc):
    """The parent's gap (PERF.md §7.24 a), still there for whoever asks for
    it: told `slot=0`, the VC's partial lies under another duty than the
    peers', and two peers alone are below t."""
    reg = vc.registration(1)

    async def main():
        await vc.vapi.submit_registration(vc.pubkeys[1], reg, vc.partial(1, reg), slot=0)
        await vc.peer_set(2, [1])
        await vc.peer_set(3, [1])

    asyncio.run(main())
    assert vc.aggregated == []


# -- the coalescer's rules on this mix: nothing kind-specific --------------------


def _waves(reg_lanes=8, att_lanes=3):
    """A registration wave of 4 sets of `reg_lanes` lanes, every lane its
    own root, and the attester wave of the same slot (4 sets of
    `att_lanes`), each set hinted as the node's submitters hint it; both
    kinds' duties share their deadline (core/deadline: slot start + 60 s)."""
    everyone = frozenset({1, 2, 3, 4})
    out = {}
    for duty, lanes in ((Duty(7, DutyType.BUILDER_REGISTRATION), reg_lanes),
                        (Duty(7, DutyType.ATTESTER), att_lanes)):
        key = (duty, frozenset(range(lanes)))
        out[str(duty.type)] = [
            ([CP._lane(bytes([sender, i, int(duty.type == DutyType.ATTESTER)]) * 10 + b"\0\0")
              for i in range(lanes)], ((key, WaveSet(sender, everyone, 4)),))
            for sender in (1, 2, 3, 4)]
    return out


def _submit_all(coal, jobs, deadline=None):
    return [asyncio.create_task(coal.verify(items, deadline=deadline, wave=hint))
            for items, hint in jobs]


def test_a_registration_wave_closes_complete_on_the_rosters_senders(clock):  # noqa: F811
    coal, fake, stats = CP._coalescer()

    async def main():
        sets = _submit_all(coal, _waves()[KIND][:3])
        await CP._settle()
        assert fake.verify_calls == 0 and set(coal._timers) == {KIND}  # waits for the fourth
        return await CP._all(*sets, *_submit_all(coal, _waves()[KIND][3:]))

    verdicts = asyncio.run(main())
    assert verdicts == [[True] * 8] * 4
    (s,) = stats
    assert s.duty_types == (KIND,) and s.window_closed_by == "complete"
    assert (s.jobs, s.lanes, s.sets_expected, s.sets_seen, s.sets_awaited) == (4, 32, 4, 4, 4)
    assert coal.windows_closed == {"complete": 1} and coal.windows_closed_short == 0


@pytest.mark.parametrize("shuffle", range(6))
def test_a_registration_flush_is_one_kind_however_the_sets_interleave(clock, shuffle):  # noqa: F811
    import random

    coal, fake, stats = CP._coalescer()
    waves = _waves()
    order = [(kind, k) for kind, jobs in waves.items() for k in range(4)]
    random.Random(f"register/{shuffle}").shuffle(order)

    async def main():
        tasks = []
        for kind, k in order:
            tasks += _submit_all(coal, [waves[kind][k]], deadline=2000.0)
            await CP._settle(2)
        return await CP._all(*tasks)

    asyncio.run(main())
    assert sorted(s.duty_types for s in stats) == [("attester",), (KIND,)]
    assert all(s.window_closed_by == "complete" and s.window_parts == 1 for s in stats)
    assert {s.duty_types[0]: s.lanes for s in stats} == {"attester": 12, KIND: 32}


def test_a_packed_registration_flush_yields_to_an_attester_window_that_is_armed(clock):  # noqa: F811
    """Same deadline, fewer lanes: the attester wave is the more urgent
    (`_urgency`). The registration wave is whole and packed while the
    attester wave has one set in; it asks for the device only once the
    attester's flush has, and says for how long it yielded and to whom."""
    coal, fake, stats = CP._coalescer()
    waves = _waves()

    async def main():
        att = _submit_all(coal, waves["attester"][:1], deadline=2000.0)
        await CP._settle()
        reg = _submit_all(coal, waves[KIND], deadline=2000.0)
        await CP._settle(20)
        assert coal.windows_closed == {"complete": 1} and set(coal._timers) == {"attester"}
        assert fake.verify_calls == 0 and len(coal._yielding) == 1
        assert coal._more_urgent(KIND, (2000.0, 32)) == {"attester"}
        clock.now += 0.2
        att += _submit_all(coal, waves["attester"][1:], deadline=2000.0)
        return await CP._all(*att, *reg)

    asyncio.run(main())
    first, second = stats
    assert (first.duty_types, first.turn_yielded_s, first.turn_yielded_to) == (("attester",), 0.0, "")
    assert second.duty_types == (KIND,) and second.turn_yielded_to == "attester"
    assert second.turn_yielded_s == pytest.approx(0.2) and coal.turns_yielded == 1


def test_a_registration_flush_with_no_attester_window_armed_goes_at_once(clock):  # noqa: F811
    """The cell's own order: the registration wave is whole four seconds
    before the attester trigger, nothing else is armed, and its flush is
    dispatched without a yield; the attester wave that comes while it is on
    the device takes the next turn (a dispatched program is not interrupted)."""
    coal, fake, stats = CP._coalescer()
    waves = _waves()

    async def main():
        reg = await CP._all(*_submit_all(coal, waves[KIND], deadline=2000.0))
        assert fake.verify_calls == 1 and not coal._timers and not coal._yielding
        return reg, await CP._all(*_submit_all(coal, waves["attester"], deadline=2000.0))

    asyncio.run(main())
    assert [s.duty_types for s in stats] == [(KIND,), ("attester",)]
    assert [(s.turn_yielded_s, s.turn_yielded_to) for s in stats] == [(0.0, "")] * 2
    assert coal.turns_yielded == 0


def test_an_attester_flush_yields_to_no_registration_window(clock):  # noqa: F811
    coal, fake, stats = CP._coalescer()
    waves = _waves()

    async def main():
        reg = _submit_all(coal, waves[KIND][:1], deadline=2000.0)
        await CP._settle()
        assert coal._collecting_urgency(KIND) == (2000.0, 32)  # the lanes it will have
        att = await CP._all(*_submit_all(coal, waves["attester"], deadline=2000.0))
        assert fake.verify_calls == 1 and set(coal._timers) == {KIND}
        reg += _submit_all(coal, waves[KIND][1:], deadline=2000.0)
        return att, await CP._all(*reg)

    asyncio.run(main())
    assert [(s.duty_types, s.turn_yielded_to) for s in stats] == [(("attester",), ""), ((KIND,), "")]


# -- the deployment's files -------------------------------------------------------

JOINED = ("flush_window_s", "flush_pack_s", "program_s.verify", "program_s.recombine",
          "device_busy_s.verify", "entry_self_s", "qbft_decide_s", "agg_bcast_self_s",
          "svc_queue_s", "window_wait_s", "idle_s.consensus", "idle_s.awaiting_input",
          "idle_s.entry", "idle_s.window", "idle_s.pack", "duty_p50_s.attester",
          "kinds_per_flush", "lane_yield_s")
NEW = ("duty_p50_s.registration", "program_s.verify.registration",
       "program_s.recombine.registration", "vapi_submits_per_wave.registration",
       "roots_hashed_per_wave.registration", "flush_pack_s.registration")
# lists the cell stays out of, and why (PERF.md §3)
LEFT_OUT = ("wave_host_s", "flushes_per_wave", "lane_order_flips", "window_wait_s.verify",
            "sets_short_per_wave", "sets_invalid_per_wave", "duty_p50_s.sync_message",
            "program_s.verify.sync_message", "program_s.recombine.sync_message",
            "vapi_submits_per_wave.sync_message")


def _config(name="dv-3of4-1k-reg"):
    return json.loads((REPO / f"benchmark/configs/{name}.json").read_text())


def _mix():
    return json.loads((REPO / "benchmark/mixes/attest-register.json").read_text())


def test_the_cell_is_in_the_manifest_with_its_per_layer_metrics():
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    assert [w["name"] for w in man["workloads"]].index(CELL) == 5
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 0
    cell = M.load_cell(REPO, CELL, man)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "dv-3of4-1k-reg", "attest-register")
    assert [m.name for m in cell.end_to_end] == ["duty_p50_s", "duty_p95_s", "setup_s"]
    names = tuple(m.name for m in cell.per_layer)
    assert names == JOINED + NEW and not set(names) & set(LEFT_OUT)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(M.load_reader(REPO, man, m.reader))
        assert m.moves in (None, "duty_p50_s")
    assert tuple(e["name"] for e in man["per_layer"][28:34]) == NEW
    for entry in man["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL]
        elif entry["name"] in JOINED:
            assert entry["workloads"][-1] == CELL  # appended, nothing else moved
    assert set(JOINED) | set(LEFT_OUT) == {e["name"] for e in man["per_layer"][:28]}
    (entry,) = [c for c in man["configs"] if c["name"] == "dv-3of4-1k-reg"]
    cfg = _config()
    assert cfg["source"] == entry["source"] and sorted(cfg["reduced"]) == entry["reduced"]
    assert entry["reduced"] == ["committees_per_slot", "keystore_kdf_c", "msm",
                                "registrations_per_batch"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("SubmitValidatorRegistrations", "Recaster", "ValidatorRegistrationV1",
                 "createcluster.go", "1,000"):
        assert word in entry["source"]
    (workload,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert len(workload["why"]) <= 200 and "registrations" in workload["why"]
    assert len(json.dumps(man)) < 64 * 1024


def test_the_configuration_is_dv_3of4_1k_but_the_named_keys():
    cfg, base = _config(), _config("dv-3of4-1k")
    differ = {key for key in set(cfg) | set(base) if cfg.get(key) != base.get(key)}
    assert differ <= {"name", "source", "deployment", "duty_types", "registrations_per_batch",
                      "registrations_per_batch_published", "registration_every_slots",
                      "programs", "requires", "guarantees", "guarantees_exercised", "reduced",
                      "assumed", "node", "coalesce_window_s", "size_tried",
                      "measured_program_seconds"}
    sync = _config("dv-3of4-1k-sync")
    # the node is dv-3of4-1k's, or the sync cell's longer patience for an
    # awaited set (the harness's signers: `assumed.coalesce_window_s` says
    # which and what arrival spread the chip showed)
    assert (cfg["node"], cfg["coalesce_window_s"]) in (
        (base["node"], base["coalesce_window_s"]), (sync["node"], sync["coalesce_window_s"]))
    assert "p44a" in cfg["assumed"]["coalesce_window_s"]  # the arrival spread the chip showed
    assert cfg["duty_types"] == ["attester", "registration"] == _mix()["duties"]
    batch = cfg["registrations_per_batch"]
    assert batch in (128, 64) and cfg["registrations_per_batch_published"] == 1000
    assert cfg["registration_every_slots"] == 1
    if batch == 64:  # the one fallback: the sync cell's executables, and what 128 showed
        assert cfg["programs"] == sync["programs"] and "128" in cfg["size_tried"]
    own = [f"step_rlc_dec@{batch}", f"verify_rlc_dec@{4 * batch}"]
    assert cfg["programs"] == base["programs"][:2] + own + ["g1dec@512"]
    assert cfg["requires"] == ["charon_tpu.core.validatorapi.ValidatorAPI.submit_registrations"]
    assert M.unresolved(cfg["requires"]) == []
    for key, value in base["guarantees"].items():
        assert cfg["guarantees"][key] == value  # none weaker
    assert len(cfg["guarantees"]) == len(base["guarantees"]) + 2 and all(cfg["guarantees"].values())
    assert set(cfg["guarantees_exercised"]) - {"not_weakened"} <= set(cfg["guarantees"])
    assert {**base["reduced"], "registrations_per_batch": cfg["reduced"][
        "registrations_per_batch"]} == cfg["reduced"]
    assert "1,000" in cfg["reduced"]["registrations_per_batch"]
    for key in ("registration_every_slots", "registration_timestamp", "fee_recipient_gas_limit",
                "programs_order", "lane_order"):
        assert key in cfg["assumed"]
    # the four programs' seconds as the chip gave them: nothing pending
    seconds = cfg["measured_program_seconds"]
    assert set(seconds) - {"origin"} == set(cfg["programs"]) - {"g1dec@512"}
    assert all(0.5 < seconds[p] < 2.0 for p in cfg["programs"][:4])
    assert "PENDING" not in json.dumps(cfg)


def test_the_mix_is_the_issues_table():
    mix = _mix()
    assert {k: v for k, v in mix.items() if k not in ("name", "description")} == {
        "duties": ["attester", "registration"], "slots": "window", "send_jitter_ms": 30,
        "silent_operators": [],
        "fault": {"kind": "flip_byte", "operator": "last", "slots": "last", "partials": 1,
                  "duties": ["registration"]}}
    assert M.load_duty("registration").OFFSET == 0.0


@pytest.mark.parametrize("seed", [1, 4400000017, 2**31 + 12345])
def test_the_mix_lands_on_the_programs_the_configuration_lists(seed):
    cfg = _config()
    plan = T.make_plan(cfg, _mix(), seed)
    T.check_programs(plan, cfg)
    assert set(cfg["programs"]) == plan.flush_shapes() | {"g1dec@512"}
    attester, reg = plan.kinds
    batch = cfg["registrations_per_batch"]
    assert reg.shapes(plan) == {f"verify_rlc_dec@{4 * batch}", f"step_rlc_dec@{batch}"}
    assert attester.shapes(plan) == {"verify_rlc_dec@128", "step_rlc_dec@32"}
    # three slots anywhere in the epoch: three batches of distinct validators
    for first in (0, 5, 29):
        members = [reg.members(plan, s) for s in range(first, first + 3)]
        assert [len(m) for m in members] == [batch] * 3
        assert len({v for m in members for v in m}) == 3 * batch
    assert plan.fault.duties == ("registration",) and plan.forged(9, 4, 9, "registration")
    assert not plan.forged(9, 4, 9, "attester")


def _flush(kinds, verify_jobs=4, hashed=(), pack=None, decode=()):
    return (10.0, types.SimpleNamespace(duty_types=kinds, verify_jobs=verify_jobs,
                                        decode_hashed=hashed, pack_span=pack, decode_spans=decode))


def test_the_new_reader_reads_one_kinds_verify_flushes_and_none_where_there_are_none():
    man = M.load_manifest(REPO)
    read = M.load_reader(REPO, man, "flush_stat_of_kind")
    run = types.SimpleNamespace(in_window=lambda ts: True, flushes=[
        _flush(("attester",), hashed=(20, 12), pack=(1.0, 1.5)),
        _flush((KIND,), hashed=(100, 28, 0, 0), pack=(2.0, 2.25), decode=((1.0, 1.5), (1.2, 1.45))),
        _flush((KIND,), verify_jobs=0, hashed=(0,), pack=(3.0, 3.5)),  # its recombine flush
        _flush((KIND,), hashed=(130,), pack=(4.0, 4.5)),
        _flush((KIND,), hashed=(140, 4), pack=(5.0, 5.125), decode=((4.0, 4.25),))])
    assert read(run, field="hashed", duty_type=KIND) == 130.0
    assert read(run, field="pack", duty_type=KIND) == pytest.approx(0.5)
    assert read(run, field="hashed", duty_type="attester") == 32.0
    with pytest.raises(ValueError):
        read(run, field="window", duty_type=KIND)
    # a run with no registration flush, and a program from before `duty_types`
    none = types.SimpleNamespace(in_window=lambda ts: True, flushes=[
        _flush(("attester",), hashed=(31,), pack=(1.0, 1.5)),
        (11.0, types.SimpleNamespace(verify_jobs=4, decode_hashed=(3,), pack_span=None,
                                     decode_spans=()))])
    for name in NEW:
        spec = json.loads((REPO / f"benchmark/metrics/{name}.json").read_text())
        if spec["reader"] == "flush_stat_of_kind":
            assert read(none, **spec["params"]) is None


def test_every_new_metric_reads_none_from_a_run_with_no_registration(monkeypatch):
    """The parent under this PR's benchmark files never gets this far (the
    configuration's `requires`), and a reader of a span or a counter the
    program lacks returns nothing and does not raise."""
    from benchmark import nodespans

    man = M.load_manifest(REPO)
    monkeypatch.setattr(nodespans, "node_spans", lambda: [])
    empty = types.SimpleNamespace(
        in_window=lambda ts: True, flushes=[], programs=[], duties=[], spans=[], slots=[7],
        window=(0.0, 12.0), slot_duration=12.0, gave_up=0.0)
    for name in NEW:
        spec = json.loads((REPO / f"benchmark/metrics/{name}.json").read_text())
        entry = next(e for e in man["per_layer"] if e["name"] == name)
        assert (spec["unit"], spec["better"], spec["source"]) == (
            entry["unit"], entry["better"], entry["source"])
        assert M.load_reader(REPO, man, spec["reader"])(empty, **spec["params"]) is None


# -- the rehearsal: the program's own path, no patch ------------------------------


@functools.cache
def _rehearse(*extra):
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/tests/rehearse_register.py"), "--unpatched", *extra],
        capture_output=True, text=True, timeout=240, cwd=str(REPO))
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc.returncode, lines, proc.stderr


def _ok(line):
    return all(c == {"value": 0, "limit": 0} for c in line["checks"].values())


@pytest.mark.parametrize("mode", [(), ("--silent",), ("--forged",)],
                         ids=["four-senders", "bare-quorum", "a-forged-set"])
def test_the_unpatched_rehearsal_ends_correct(mode):
    rc, (info, line, seen), err = _rehearse(*mode)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and line["attempted"] == 13 and line["failed"] == 0
    assert _ok(line) and seen["patches"] == [] and info["info"]["patched"] == "none"
    assert seen["records"] == {"attester": 7, "registration": 6}
    assert len(seen["vc_rounds"]) == 1
    if mode == ("--forged",):
        assert info["info"]["forged_sets"] == {"sent": 1, "rejected": 1}
    if mode == ("--silent",):
        assert seen["peers"]["2"] == {"sent_sets": 0, "forged_sets": 0}


def test_behind_the_plane_the_request_is_two_flushes_both_complete():
    """§7.24 b, closed: the parent's router made 6 flushes of the request,
    every one closed by its timer."""
    rc, (_info, line, seen), err = _rehearse("--plane")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and _ok(line)
    mine = [f for f in seen["flushes"] if f["duty_types"] == [KIND]]
    assert [(f["verify_jobs"], f["recombine_jobs"], f["lanes"], f["closed_by"]) for f in mine] == [
        (4, 0, 24, "complete"), (0, 1, 6, "complete")]
    assert all(len(f["duty_types"]) == 1 for f in seen["flushes"])  # kinds_per_flush 1.0
    assert all(f["closed_by"] == "complete" for f in seen["flushes"])


def test_a_window_opened_after_slot_zeros_deadline_changes_nothing():
    """A chip run's set-up is minutes: nothing of the path may lean on
    slot 0 (its deadline is 30 s after genesis in the rehearsal's clock)."""
    rc, (_info, line, seen), err = _rehearse("--silent", "--late", "31")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and line["attempted"] == 13 and _ok(line)
