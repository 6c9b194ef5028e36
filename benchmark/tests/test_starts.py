"""A duty that validator clients start is a file too (ISSUE 43): a kind may
say `STARTS = "vc"` and is then driven on the slot clock — the node's VC and
every peer at the instant the request is due, with no scheduler duty and no
QBFT decision behind it; a configuration may name what it `requires` of the
program; the plain
reference of a builder registration's signing root; and the kind that proves
it all on the CPU, `duties/registration.py`, with the patches the parent
program needs (tests/registerpatch.py). The five cells' plans and shapes are
the parent's, value for value (tests/data/parent_plans.json).

    python -m pytest benchmark/tests/test_starts.py -q -p no:cacheprovider
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import hashlib
import json
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import check, manifest as M, reference_registration as RR  # noqa: E402
from benchmark import serve, traffic as T  # noqa: E402
from benchmark.tests import helpers, rehearse_register  # noqa: E402

PLANS = json.loads((REPO / "benchmark/tests/data/parent_plans.json").read_text())
REG_MIX = {"name": "m", "duties": ["attester", "registration"], "send_jitter_ms": 30,
           "silent_operators": [], "fault": {"kind": "none"}}


def _config(**more):
    return dict(helpers.REHEARSAL, registrations_per_batch=6, registration_every_slots=2, **more)


# -- the five cells keep their plans and their shapes --------------------------


@pytest.mark.parametrize("case", sorted(PLANS["cases"]))
def test_a_cells_plan_and_shapes_are_the_parents(case):
    """tests/data/parent_plans.json was written from the parent of the PR
    that let a kind say who starts it (commit 9c095bc): `make_plan` of every cell on seeds 0-4."""
    name, seed = case.rsplit("/", 1)
    cell = M.load_cell(REPO, name)
    plan = T.make_plan(cell.config, cell.traffic, int(seed))
    T.check_programs(plan, cell.config)
    want = PLANS["cases"][case]
    assert hashlib.sha256(repr(plan).encode()).hexdigest() == want["plan_repr_sha256"]
    assert sorted(plan.flush_shapes()) == want["shapes"]
    assert {k.NAME: [len(k.members(plan, s)) for s in range(plan.slots_per_epoch)]
            for k in plan.kinds} == want["duties_a_slot"]
    assert not any(serve.vc_started(kind) for kind in plan.kinds)
    assert "requires" not in cell.config


# -- what a configuration requires of the program -------------------------------


def test_requires_names_what_is_missing_of_the_program():
    assert M.unresolved([]) == []
    have = ["charon_tpu.core.validatorapi.ValidatorAPI.submit_registration",
            "charon_tpu.core.validatorapi.ValidatorAPI", "charon_tpu.core.deadline",
            "benchmark.traffic.Plan.wave_shapes"]
    lack = ["charon_tpu.core.validatorapi.ValidatorAPI.submit_registrations",
            "charon_tpu.core.no_such_module.Thing", "no_such_package.x", "charon_tpu.core.deadline.X.y"]
    assert M.unresolved(have + lack) == lack
    for cell in (w["name"] for w in M.load_manifest(REPO)["workloads"]):
        assert "requires" not in M.load_cell(REPO, cell).config  # the five state none


def test_a_requirement_that_does_not_resolve_ends_the_run_before_boot():
    name = "charon_tpu.core.validatorapi.ValidatorAPI.submit_registrations"
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/tests/rehearse_register.py"), "--requires", name],
        capture_output=True, text=True, timeout=120, cwd=str(REPO))
    took = time.monotonic() - t0
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 3 and took < 30
    assert line["correct"] is False and line["attempted"] == 0 and line["metrics"] == {}
    assert line["error"].startswith("before boot:") and name in line["error"]
    assert "phase cluster" not in proc.stderr  # nothing booted, nothing compiled


# -- the plain reference of a registration's signing root -----------------------


@pytest.mark.parametrize("seed", [1, 4300000013, 2**31 + 12345])
def test_the_plain_registration_signing_root_is_the_programs_ssz_root(seed):
    from charon_tpu.core.eth2data import SignedData
    from charon_tpu.eth2util.registration import ValidatorRegistration
    from charon_tpu.eth2util.signing import ForkInfo

    reg = M.load_duty("registration")
    digest = hashlib.sha256(f"reg/{seed}".encode()).digest()
    fields = (reg.fee_recipient(seed, 5), reg.GAS_LIMIT, 1_790_000_000 + seed % 1000,
              (digest + digest)[:48])
    genesis_version = digest[:4]
    fork = ForkInfo(genesis_validators_root=digest, fork_version=bytes.fromhex("04000000"),
                    genesis_fork_version=genesis_version)
    program = SignedData("registration", ValidatorRegistration(*fields)).signing_root(fork, 9)
    assert RR.registration_signing_root(fields, genesis_version) == program
    # the later fork's version and the chain's genesis validators root are not in it
    assert RR.registration_signing_root(fields, fork.fork_version) != program
    for i, other in enumerate((bytes(20), fields[1] + 1, fields[2] + 1, bytes(48))):
        changed = fields[:i] + (other,) + fields[i + 1:]
        assert RR.registration_signing_root(changed, genesis_version) != program
    assert RR.registration_signing_root(fields, genesis_version,
                                        bytes.fromhex("01000000")) != program


def test_the_plain_registration_root_against_an_answer_anyone_can_recompute():
    """sha256 alone: four leaves, the 48-byte key two chunks under one node."""
    sha = lambda *parts: hashlib.sha256(b"".join(parts)).digest()  # noqa: E731
    fields = (b"\x11" * 20, 30_000_000, 1_790_000_000, b"\xab" * 48)
    leaves = [fields[0] + bytes(12), (30_000_000).to_bytes(32, "little"),
              (1_790_000_000).to_bytes(32, "little"),
              sha(b"\xab" * 32, b"\xab" * 16 + bytes(16))]
    root = sha(sha(leaves[0], leaves[1]), sha(leaves[2], leaves[3]))
    assert RR.registration_root(fields) == root
    domain = bytes.fromhex("00000001") + sha(bytes(4) + bytes(28), bytes(32))[:28]
    assert RR.registration_signing_root(fields, bytes(4)) == sha(root, domain)
    with pytest.raises(ValueError):
        RR.registration_root((b"\x11" * 19, 1, 2, b"\xab" * 48))


def test_reference_registration_imports_the_reference_alone():
    import ast

    tree = ast.parse((REPO / "benchmark/reference_registration.py").read_text())
    imported = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    assert imported == {"__future__", "benchmark.reference"}


# -- the kind's schedule and its record -----------------------------------------


@pytest.mark.parametrize("seed", [1, 4300000013, 2**31 + 12345])
def test_a_batch_is_the_next_ranks_of_the_seeded_order_every_e_slots(seed):
    plan = T.make_plan(_config(), REG_MIX, seed)
    reg = plan.kinds[1]
    assert reg.NAME == "registration" and serve.vc_started(reg)
    order = sorted(range(14), key=plan.rank.__getitem__)
    assert [reg.members(plan, s) for s in (1, 3, 5)] == [[], [], []]
    assert reg.members(plan, 0) == order[:6] and reg.members(plan, 2) == order[6:12]
    assert reg.members(plan, 4) == order[12:] + order[:4]  # wraps mod validators
    assert reg.shapes(plan) == plan.wave_shapes(6) == {"verify_rlc_dec@32", "step_rlc_dec@8"}
    assert str(reg.duty(plan, 6)) == "6/builder_registration"
    # three slots and E = 3 hold one batch wherever the window starts
    every3 = T.make_plan(dict(_config(), registration_every_slots=3), REG_MIX, seed)
    for first in range(7):
        assert sum(1 for s in range(first, first + 3) if reg.members(every3, s)) == 1
    with pytest.raises(ValueError):
        reg.members(T.make_plan(dict(_config(), registrations_per_batch=15), REG_MIX, seed), 0)


def _scene(plan, genesis):
    pubkeys = ["0x" + hashlib.sha256(b"pk%d" % v).hexdigest() * 2 for v in range(plan.validators)]
    pubkeys = [pk[:98] for pk in pubkeys]
    cluster = types.SimpleNamespace(pubkeys=pubkeys,
                                    validators={pk: v for v, pk in enumerate(pubkeys)})
    return serve.Scene(plan, cluster, genesis=genesis)


def test_a_registration_is_made_from_the_seed_and_read_back_into_its_record():
    plan = T.make_plan(_config(), REG_MIX, 11)
    reg = plan.kinds[1]
    genesis = 1_790_000_000.37
    scene = _scene(plan, genesis)
    regs = reg.unsigned(scene, 6)
    assert reg.unsigned(scene, 6) is regs  # once for every operator
    assert [scene.cluster.validators[pk] for pk in regs] == reg.members(plan, 6)
    at = 1_790_000_019  # the first whole second of slot 6, which starts at ...18.37
    for pk, r in regs.items():
        vidx = scene.cluster.validators[pk]
        assert (r.fee_recipient, r.gas_limit, r.timestamp, r.pubkey) == (
            reg.fee_recipient(11, vidx), 30_000_000, at, bytes.fromhex(pk[2:]))
        # the slot is the timestamp's, whatever the clock says on arrival
        assert reg.submitted(scene, r, b"s" * 96) == (
            6, vidx, b"s" * 96, (r.fee_recipient, 30_000_000, at, r.pubkey))
        record = serve.DutyRecord("registration", 6, vidx, pk, genesis + 6 * 3.0)
        fields, root = reg.expected(plan, record, (bytes(4), b"g" * 32))
        assert fields == (r.fee_recipient, 30_000_000, at, r.pubkey)
        assert root == RR.registration_signing_root(fields, bytes(4))
    stranger = types.SimpleNamespace(pubkey=b"\x01" * 48, timestamp=at)
    assert reg.submitted(scene, stranger, b"") is None
    # a whole-second genesis: the slot's start is its own first second
    whole = _scene(plan, 1_790_000_000.0)
    (r, *_), = [list(reg.unsigned(whole, 2).values())]
    assert r.timestamp == 1_790_000_006 and reg.submitted(whole, r, b"")[0] == 2


RESENDS = {
    # a later delivery -> how many broadcasts its record counts, by content alone
    "the_recasters_byte_equal_resend": (lambda r, sig: (r, sig), 1),
    "a_copy_that_is_byte_equal": (lambda r, sig: (dataclasses.replace(r), bytes(sig)), 1),
    "another_signature": (lambda r, sig: (r, b"t" * 96), 2),
    "another_fee_recipient": (lambda r, sig: (dataclasses.replace(r, fee_recipient=bytes(20)), sig), 2),
    "another_gas_limit": (lambda r, sig: (dataclasses.replace(r, gas_limit=1), sig), 2),
}


@pytest.mark.parametrize("case", sorted(RESENDS))
def test_a_later_delivery_is_the_recasters_only_if_it_is_byte_equal_to_the_first(case, monkeypatch):
    """Through the harness's own stamp on the beacon's `submit_registration`,
    the second delivery an epoch later (4 slots of 3 s): `duties_duplicated`
    counts whatever differs from the first, so `correct` is false, and
    nothing that does not."""
    again, broadcasts = RESENDS[case]
    plan = T.make_plan(_config(), REG_MIX, 11)
    reg = plan.kinds[1]
    server = serve.Server(types.SimpleNamespace(config=_config()), plan, 11, None, None, None, set())
    server.scene = scene = _scene(plan, time.time() - 6 * 3.0)
    got = []

    async def inner(*args):
        got.append(args)

    submit = server._stamped(reg, inner)
    regs = reg.unsigned(scene, 6)
    for pk in regs:
        vidx = scene.cluster.validators[pk]
        server._records[(reg.NAME, 6, vidx)] = serve.DutyRecord(
            reg.NAME, 6, vidx, pk, scene.genesis + 6 * 3.0)
    first_pk = next(iter(regs))

    async def drive():
        for r in regs.values():
            await submit(r, b"s" * 96)
        epoch_on = time.time() + 4 * 3.0
        monkeypatch.setattr(time, "time", lambda: epoch_on)
        await submit(*again(regs[first_pk], b"s" * 96))

    asyncio.run(drive())
    assert len(got) == len(regs) + 1  # the beacon gets every delivery all the same
    records = list(server._records.values())
    assert [d.broadcasts for d in records] == [broadcasts] + [1] * (len(records) - 1)
    first = records[0]
    assert first.signature == b"s" * 96 and first.data[0] == regs[first_pk].fee_recipient
    server.run.duties = records
    for d in records:  # the VC's part and the group signature are not this test's
        d.root = reg.expected(plan, d, (bytes(4), bytes(32)))[1]
    monkeypatch.setattr(check.reference, "sign", lambda secret, root: b"s" * 96)
    monkeypatch.setattr(check.reference, "secret_to_public_key",
                        lambda secret: bytes.fromhex(secret[2:]))
    cluster = types.SimpleNamespace(group_secrets={d.pubkey: d.pubkey for d in records})
    checks = check.compare(server.run, cluster, plan, (bytes(4), bytes(32)), {}, 0, 0, 0)
    assert {k: c["value"] for k, c in checks.items() if c["value"]} == (
        {"duties_duplicated": 1} if broadcasts == 2 else {})
    assert check.verdict(checks) is (broadcasts == 1)


def test_a_first_delivery_in_a_later_epoch_than_its_timestamps_fills_the_record():
    """A broadcast that crossed an epoch start is late, not missing."""
    plan = T.make_plan(_config(), REG_MIX, 11)
    reg = plan.kinds[1]
    scene = _scene(plan, time.time() - 40 * 3.0)  # ten epochs after slot 6
    (r, *_rest) = reg.unsigned(scene, 6).values()
    assert reg.submitted(scene, r, b"s" * 96)[:2] == (6, scene.cluster.validators["0x" + r.pubkey.hex()])
    assert reg.submitted(scene, r, b"s" * 96) is None  # and then the recaster's


def test_the_registration_waves_shapes_are_its_own_bucket():
    """No rule for a wave above the compiled buckets: 6 duties of 4 senders
    are `verify_rlc_dec@32` / `step_rlc_dec@8`, and the list is held to them."""
    programs = ["verify_rlc_dec@16", "step_rlc_dec@4", "verify_rlc_dec@32", "step_rlc_dec@8",
                "g1dec@512"]
    config = _config(programs=programs)
    plan = T.make_plan(config, REG_MIX, 3)
    assert plan.kinds[1].shapes(plan) == {"verify_rlc_dec@32", "step_rlc_dec@8"}
    T.check_programs(plan, config)
    for lacking in (programs[:2] + programs[4:], programs + ["h2c@4"]):
        with pytest.raises(T.TrafficError):
            T.check_programs(plan, dict(config, programs=lacking))
    big = {**config, "validators": 1000, "slots_per_epoch": 32, "registrations_per_batch": 256}
    assert T.make_plan(big, dict(REG_MIX, duties=["registration"]), 3).flush_shapes() == {
        "verify_rlc_dec@1024", "step_rlc_dec@256"}


# -- who starts a duty: the slot clock, or the scheduler and QBFT ----------------


def test_a_vc_kinds_rounds_fire_at_due_in_the_windows_slots_and_no_other():
    config = dict(_config(), slot_duration_s=0.2)
    plan = T.make_plan(config, REG_MIX, 5)
    fired = []

    async def drive():
        gate = serve.Gate()
        scene = _scene(plan, time.time())

        async def fire(kind, slot):
            fired.append((kind.NAME, slot, time.time() - (scene.genesis + slot * 0.2)))

        tasks = serve.started_rounds(scene, gate, fire)
        assert len(tasks) == 1  # the attester is the scheduler's
        await asyncio.sleep(0.3)
        assert fired == []  # before the window is known: nothing
        gate.serve(3, 4)  # slots 3..6: batches in 4 and 6
        await asyncio.wait_for(tasks[0], 3.0)

    asyncio.run(drive())
    assert [(k, s) for k, s, _late in fired] == [("registration", 4), ("registration", 6)]
    assert all(0.0 <= late < 0.15 for _k, _s, late in fired)
    with pytest.raises(ValueError, match="STARTS"):
        serve.vc_started(types.SimpleNamespace(NAME="x", STARTS="beacon"))


def test_a_peer_sends_a_vc_kinds_set_with_no_decision_and_a_decided_kinds_after_one():
    from charon_tpu.core.types import Duty, DutyType

    def peer(index, mix):
        plan = T.make_plan(_config(), mix, 5)
        gate, spans = serve.Gate(), []
        gate.serve(2, 2)
        p = serve.HostPeer(_scene(plan, 1000.0), index, [], 1000.0, gate, spans)
        p.sent = []

        async def send(duty, unsigned_set, share_idx):
            p.sent.append((str(duty), sorted(unsigned_set), share_idx))

        p._send = send
        return p, plan, spans

    async def drive(p, plan):
        attester, reg = plan.kinds
        await p._started(reg, 2)
        # a decision of a kind that is not decided (none comes) sends nothing
        await p._decided(Duty(2, DutyType.BUILDER_REGISTRATION), {"0xaa": object()})
        await p._decided(Duty(2, DutyType.ATTESTER), {"0xbb": object()})
        await p._decided(Duty(9, DutyType.ATTESTER), {"0xbb": object()})  # outside the window
        await p._decided(Duty(2, DutyType.PROPOSER), {"0xcc": object()})  # no kind of the mix
        await asyncio.gather(*p._sends)

    p, plan, spans = peer(2, REG_MIX)
    asyncio.run(drive(p, plan))
    pubkeys = sorted(p.scene.cluster.pubkeys[v] for v in plan.kinds[1].members(plan, 2))
    assert p.sent == [("2/builder_registration", pubkeys, 3), ("2/attester", ["0xbb"], 3)]
    assert [name for name, _a, _b in spans] == ["qbft_decided"]  # the attester's alone
    # a silent operator: its QBFT still decides, it sends nothing of either kind
    p, plan, spans = peer(1, dict(REG_MIX, silent_operators=[2]))
    asyncio.run(drive(p, plan))
    assert p.sent == [] and [name for name, _a, _b in spans] == ["qbft_decided"]


def test_the_nodes_vc_takes_a_decided_kind_from_the_scheduler_and_a_vc_kind_from_the_clock():
    from charon_tpu.core.types import Duty, DutyType

    plan = T.make_plan(_config(), REG_MIX, 5)
    wd = types.SimpleNamespace(note=lambda text: notes.append(text))
    notes, rounds = [], []
    server = serve.Server(types.SimpleNamespace(config=_config()), plan, 5, wd, None, None, set())
    server.kinds, server.scene = serve.kinds_by_type(plan), _scene(plan, 1000.0)
    server.gate.serve(2, 2)
    for kind in plan.kinds:
        async def vc_round(srv, duty, defs, name=kind.NAME):
            rounds.append((name, str(duty), sorted(defs)))
            if name == "registration" and duty.slot == 3:
                raise RuntimeError("400")
            return [(f"vc_{name}", 1.0, 2.0)]

        kind.vc_round, restore = vc_round, kind.vc_round
        kind._restore = restore
    try:
        async def drive():
            await server._vc_on_duty(Duty(2, DutyType.ATTESTER), {"0xbb": 1})
            await server._vc_on_duty(Duty(2, DutyType.BUILDER_REGISTRATION), {"0xaa": 1})
            await server._vc_on_duty(Duty(7, DutyType.ATTESTER), {"0xbb": 1})  # gate shut
            await server._vc_started(plan.kinds[1], 2)
            await server._vc_started(plan.kinds[1], 3)  # refused: noted, the run goes on

        asyncio.run(drive())
    finally:
        for kind in plan.kinds:
            kind.vc_round = kind._restore
    pubkeys = sorted(server.scene.cluster.pubkeys[v] for v in plan.kinds[1].members(plan, 2))
    assert rounds == [("attester", "2/attester", ["0xbb"]),
                      ("registration", "2/builder_registration", pubkeys),
                      ("registration", "3/builder_registration", [])]
    assert server.run.spans == [("vc_attester", 1.0, 2.0), ("vc_registration", 1.0, 2.0)]
    assert len(notes) == 1 and "registration round of slot 3 failed" in notes[0]
    assert server.run.duty_types == ("attester", "builder_registration")


def test_a_forged_set_is_expected_only_where_the_kind_has_a_duty():
    forged = dict(REG_MIX, fault={"kind": "flip_byte", "operator": "last", "slots": "all",
                                  "partials": 1, "duties": ["registration"]})
    plan = T.make_plan(_config(), forged, 5)
    server = serve.Server(types.SimpleNamespace(config=_config()), plan, 5, None, None, None, set())
    server.gate.serve(1, 4)
    server.run.slots = [1, 2, 3, 4]
    assert server.expected_forged_sets() == 2  # slots 2 and 4: the batches; not 1 and 3
    both = T.make_plan(_config(), dict(forged, fault=dict(forged["fault"], duties=[])), 5)
    server.plan = both
    assert server.expected_forged_sets() == 4 + 2  # the attester's every slot
    server.plan = T.make_plan(_config(), dict(forged, fault=dict(forged["fault"], slots="last")), 5)
    assert server.expected_forged_sets() == 1  # the last slot holds a batch
    server.gate.serve(1, 3)
    server.run.slots = [1, 2, 3]
    assert server.expected_forged_sets() == 0  # the last slot holds none: nothing is forged


# -- the rehearsal: the whole control flow on the CPU ---------------------------


@functools.cache
def _rehearse(*extra):
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/tests/rehearse_register.py"), *extra],
        capture_output=True, text=True, timeout=240, cwd=str(REPO))
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc.returncode, lines, proc.stderr


def _ok(line):
    return all(c == {"value": 0, "limit": 0} for c in line["checks"].values())


def test_a_vc_started_kind_runs_the_whole_control_flow_and_ends_correct():
    rc, (info, line, seen), err = _rehearse()
    assert rc == 0, err[-3000:]
    # two slots: 4 + 3 attesters, and the batch of 6 in the one even slot
    assert line["correct"] is True and line["attempted"] == 13 and line["failed"] == 0
    assert list(line["checks"]) == list(check.PER_DUTY) + [
        "forged_sets_not_rejected", "degradation_events", "compiles_in_window"]
    assert _ok(line)
    (batch_slot,) = [s for s in seen["window_slots"] if s % 2 == 0]
    # the VC's round at the instant the request was due: not before, one round
    ((kind, slot, late),) = seen["vc_rounds"]
    assert (kind, slot) == ("registration", batch_slot) and 0.0 <= late < 0.5
    rounds = [name for name, _at, _took in info["info"]["vc_spans_s"]]
    assert sorted(rounds) == sorted(2 * ["vc_attestation_data"] + ["vc_registrations"]
                                    + 3 * ["vc_sign", "http_submit"])
    # three peers' sets, each called at due, out no sooner than its jitter, and
    # no QBFT decision behind them: the six spans are the attester's (3 x 2)
    assert [s[0] for s in seen["peer_sends"]] == [2, 3, 4]
    for _idx, called, done, jitter in seen["peer_sends"]:
        assert 0.0 <= called < 0.5 and done >= jitter
    assert seen["qbft_decided_spans"] == 6
    assert all(p == {"sent_sets": 3, "forged_sets": 0} for p in seen["peers"].values())
    assert seen["patches"] == ["registration_slot_from_timestamp",
                               "registrations_one_request_one_set"]


def test_the_recasters_resend_at_an_epochs_start_is_a_delivery_and_no_broadcast():
    """Six slots of the 4-slot epoch, a batch every second slot: wherever the
    window opens, an epoch starts in it after a batch, and the node's own
    recaster sends that batch to the beacon again, byte for byte."""
    rc, (_info, line, seen), err = _rehearse("--seconds", "18")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and _ok(line)
    batches = sum(1 for s in seen["window_slots"] if s % 2 == 0)
    assert seen["records"]["registration"] == 6 * batches == 18
    assert seen["delivered"]["registration"] >= 18 + 6  # one batch again, or more
    assert seen["delivered"]["attester"] == seen["records"]["attester"]


def test_a_silent_operator_sends_no_registration_and_bare_quorum_completes_them():
    rc, (_info, line, seen), err = _rehearse("--silent")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and line["attempted"] == 13 and _ok(line)
    assert [s[0] for s in seen["peer_sends"]] == [3, 4]
    assert seen["peers"]["2"] == {"sent_sets": 0, "forged_sets": 0}
    assert seen["qbft_decided_spans"] == 6  # the silent operator's QBFT decides all the same


def test_a_forged_registration_set_is_refused_exactly_once():
    rc, (info, line, seen), err = _rehearse("--forged")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and line["attempted"] == 13 and _ok(line)
    assert info["info"]["forged_sets"] == {"sent": 1, "rejected": 1}
    assert seen["peers"]["4"] == {"sent_sets": 3, "forged_sets": 1}  # its attester sets honest


def test_the_parent_program_files_its_vcs_registrations_under_slot_zero():
    """The run without the patches, at bare quorum: the node's own VC's
    partials lie under Duty(0, builder_registration), its two peers' under
    the slot of the timestamp, and no registration reaches t."""
    rc, (_info, line, seen), err = _rehearse("--silent", "--unpatched")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False and line["attempted"] == 13 and line["failed"] == 6
    assert {k: c["value"] for k, c in line["checks"].items() if c["value"]} == {
        "duties_missing": 6}
    assert seen["patches"] == [] and len(seen["vc_rounds"]) == 1
