"""Two kinds of duty at one trigger instant (ISSUE 39, after the refused
PR 38: `dv-3of4-1k-sync.attest-sync`): a validator client's sync-committee messages
reach the plane as ONE set (one `vapi.submit`, one verify job under the wave
key the peers' sets carry; a bad partial refuses the request whole); the
deployment's configuration, mix, cell and eight metrics pass the harness's
pre-boot checks; its readers; and two rehearsals of the cell's control flow
on the CPU THROUGH the crypto-plane service path
(benchmark/tests/rehearse_twokinds.py): in every slot four flushes, one a
kind and family, each on its kind's own bucket, each closed `complete` —
honest, and with operator 4's SYNC set forged in the last slot. The
coalescer's own tests of one kind a flush are in tests/test_cryptoplane.py."""

from __future__ import annotations

import asyncio
import json
import subprocess
import sys
import types
from pathlib import Path

import aiohttp
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import manifest as M, reference as R, reference_sync as RS  # noqa: E402
from benchmark import traffic as T  # noqa: E402
from charon_tpu import tbls  # noqa: E402
from charon_tpu.app import tracer  # noqa: E402
from charon_tpu.core import eth2data as d  # noqa: E402
from charon_tpu.core.parsigex import WaveRoster, WaveSet  # noqa: E402
from charon_tpu.core.types import Duty, DutyType, pubkey_from_bytes  # noqa: E402
from charon_tpu.core.validatorapi import ValidatorAPI  # noqa: E402
from charon_tpu.core.vapi_http import VapiRouter  # noqa: E402
from charon_tpu.tbls.native_impl import NativeImpl  # noqa: E402
from tests.test_cryptoplane import FORK  # noqa: E402

CELL = "dv-3of4-1k-sync.attest-sync"
NEW = ("duty_p50_s.attester", "duty_p50_s.sync_message", "program_s.verify.sync_message",
       "program_s.recombine.sync_message", "kinds_per_flush",
       "vapi_submits_per_wave.sync_message", "lane_order_flips", "lane_yield_s")
# lists the cell stays out of, and why (PERF.md §3)
LEFT_OUT = ("flushes_per_wave", "window_wait_s.verify", "sets_short_per_wave",
            "sets_invalid_per_wave")
SLOT, MEMBERS = 41, 5


# -- a request is one set --------------------------------------------------------


class RecordingPlane:
    """A plane handle that takes the hint and judges each lane by the C++
    engine: what `ValidatorAPI._check_batch` sends, call by call."""

    wave_hints = True

    def __init__(self):
        self.calls = []

    async def verify(self, items, deadline=None, wave=None):
        self.calls.append((list(items), wave))
        return tbls.verify_batch(list(items))


@pytest.fixture
def committee():
    """A node's ValidatorAPI over HTTP for a committee of MEMBERS
    validators, operator 1's shares, and what ParSigDB would be given."""
    impl = NativeImpl()
    tbls.set_implementation(impl)
    members = []
    for i in range(MEMBERS):
        secret = impl.generate_secret_key()
        members.append((pubkey_from_bytes(impl.secret_to_public_key(secret)),
                        impl.threshold_split(secret, 4, 3)[1]))
    plane, ring, stored = RecordingPlane(), tracer.Tracer(capacity=64), []
    vapi = ValidatorAPI(
        share_idx=1, pubshares={pk: impl.secret_to_public_key(share) for pk, share in members},
        fork=FORK, slots_per_epoch=32, plane=plane, tracer=ring,
        roster=WaveRoster(range(1, 5)))

    async def store(duty, signed_set):
        stored.append((duty, signed_set))

    vapi.subscribe(store)
    router = VapiRouter(vapi, validators={pk: 100 + i for i, (pk, _s) in enumerate(members)})
    root = b"\x5a" * 32

    def message(i, share=None):
        unsigned = d.SyncCommitteeMessage(SLOT, root, 100 + i)
        to_sign = d.SignedData("sync_message", unsigned).signing_root(FORK, SLOT // 32)
        sig = impl.sign(share or members[i][1], to_sign)
        return {"slot": str(SLOT), "beacon_block_root": "0x" + root.hex(),
                "validator_index": str(100 + i), "signature": "0x" + sig.hex()}

    async def post(body):
        port = await router.start()
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.post(f"http://127.0.0.1:{port}/eth/v1/beacon/pool/sync_committees",
                                     json=body) as resp:
                    return resp.status, await resp.text()
        finally:
            await router.stop()

    return types.SimpleNamespace(members=members, plane=plane, ring=ring, stored=stored,
                                 message=message, post=post, root=root)


def _submits(ring):
    return [s for s in ring.spans if s.name == "vapi.submit"]


@pytest.mark.parametrize("k", [MEMBERS, 1])
def test_a_request_of_k_sync_messages_is_one_set(committee, k):
    """ONE `vapi.submit` span whose `count` is the request's size, ONE
    verify job of k lanes under the key a peer's set of the same members
    carries, ONE set handed on; a request of one message as it always was."""
    c = committee
    status, text = asyncio.run(c.post([c.message(i) for i in range(k)]))
    assert status == 200, text
    (span,) = _submits(c.ring)
    assert (span.attrs["duty_type"], span.attrs["count"], span.attrs["rejected"]) == (
        "sync_message", k, 0)
    ((items, wave),) = c.plane.calls
    duty = Duty(SLOT, DutyType.SYNC_MESSAGE)
    pubkeys = frozenset(pk for pk, _share in c.members[:k])
    assert len(items) == k
    assert wave == (((duty, pubkeys), WaveSet(1, frozenset({1, 2, 3, 4}), 4)),)
    # the root every lane signs is the plain reference's root of the block root
    want = RS.sync_message_signing_root(
        c.root, bytes(FORK.fork_version), bytes(FORK.genesis_validators_root))
    assert {root for _pk, root, _sig in items} == {want}
    ((stored_duty, signed_set),) = c.stored
    assert stored_duty == duty and set(signed_set) == pubkeys
    assert {p.share_idx for p in signed_set.values()} == {1}


def test_a_request_with_one_bad_partial_is_refused_whole(committee):
    c = committee
    body = [c.message(i) for i in range(MEMBERS)]
    body[3] = c.message(3, share=c.members[0][1])  # another validator's share
    status, text = asyncio.run(c.post(body))
    assert status == 400 and "pubshare verification" in text
    (span,) = _submits(c.ring)
    assert (span.attrs["count"], span.attrs["rejected"], span.status) == (MEMBERS, 1, "error")
    assert len(c.plane.calls) == 1 and len(c.plane.calls[0][0]) == MEMBERS
    assert c.stored == []  # nothing of the request reached ParSigDB


def test_a_request_for_an_unknown_validator_reaches_no_plane(committee):
    c = committee
    body = [c.message(0), dict(c.message(1), validator_index="999")]
    status, text = asyncio.run(c.post(body))
    assert status == 400 and "unknown validator index 999" in text
    assert c.plane.calls == [] and c.stored == []


# -- the deployment's files ------------------------------------------------------


def _config(name="dv-3of4-1k-sync"):
    return json.loads((REPO / f"benchmark/configs/{name}.json").read_text())


def _mix():
    return json.loads((REPO / "benchmark/mixes/attest-sync.json").read_text())


def test_the_cell_is_in_the_manifest_with_its_per_layer_metrics():
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    # the fifth cell; later PRs' cells follow it (PR 44: dv-3of4-1k-reg.attest-register)
    assert [w["name"] for w in man["workloads"]].index(CELL) == 4
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 0
    cell = M.load_cell(REPO, CELL, man)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "dv-3of4-1k-sync", "attest-sync")
    assert [m.name for m in cell.end_to_end] == ["duty_p50_s", "duty_p95_s", "setup_s"]
    names = [m.name for m in cell.per_layer]
    assert len(names) == 24 and tuple(names[-8:]) == NEW and not set(names) & set(LEFT_OUT)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(M.load_reader(REPO, man, m.reader))
        assert m.moves in (None, "duty_p50_s")
    # the manifest as PR 39 left it is its first 28 metrics: the cell's eight
    # close it, each list its own from the start; a later cell (PR 44's) is
    # appended behind, to these lists and to the manifest, and moves nothing
    older = [w["name"] for w in man["workloads"]][:4]
    assert tuple(e["name"] for e in man["per_layer"][20:28]) == NEW
    for entry in man["per_layer"][:28]:
        if entry["name"] in NEW:
            assert entry["workloads"][0] == CELL
            assert set(entry["workloads"][1:]) <= {"dv-3of4-1k-reg.attest-register"}
        elif entry["name"] not in LEFT_OUT:
            assert entry["workloads"][:5] == older + [CELL]  # appended, nothing else moved
        else:
            assert CELL not in entry["workloads"]
    for entry in man["per_layer"][28:]:
        assert CELL not in entry["workloads"]  # a later cell's own metrics
    (entry,) = [c for c in man["configs"] if c["name"] == "dv-3of4-1k-sync"]
    cfg = _config()
    assert cfg["source"] == entry["source"] and sorted(cfg["reduced"]) == entry["reduced"]
    assert entry["reduced"] == ["committees_per_slot", "keystore_kdf_c", "msm",
                                "sync_committee_members"]
    assert len(entry["source"]) <= 200 and "configs[2]" in entry["source"]
    (workload,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert len(workload["why"]) <= 200 and "sync committee" in workload["why"]


def test_the_configuration_is_dv_3of4_1k_with_a_second_kind_of_duty():
    cfg, base = _config(), _config("dv-3of4-1k")
    for key in ("operators", "threshold", "validators", "slots_per_epoch", "slot_duration_s",
                "key_table_keys", "committees_per_slot", "keystore_kdf_c", "msm", "env"):
        assert cfg[key] == base[key], key  # no width, no timing differs
    # the node as dv-3of4-1k's but for the longest a kind waits for an
    # awaited set that does not come (hand-set in both: `assumed`)
    windows = {"crypto_plane_window": 1.0, "crypto_plane_window_max": 2.0}
    assert cfg["node"] == {**base["node"], **windows} and cfg["coalesce_window_s"] == 1.0
    assert "p38a" in cfg["assumed"]["coalesce_window_s"]
    assert cfg["duty_types"] == ["attester", "sync_message"] == _mix()["duties"]
    members = cfg["sync_committee_members"]
    assert cfg["sync_committee_members_published"] == 512
    # ISSUE 39 names the size: 64, what the chip held (128 fails the slot)
    assert members == 64 and sorted(cfg["size_tried"]) == ["128", "64"]
    # the attester's two programs first, in dv-3of4-1k's order (the order
    # is part of their cache keys), then the sync wave's own two, the
    # recombine program before the verify program (`assumed.programs_order`:
    # the longer load is not the last)
    own = [f"step_rlc_dec@{members}", f"verify_rlc_dec@{4 * members}"]
    assert cfg["programs"] == base["programs"][:2] + own + ["g1dec@512"]
    assert not set(own) & set(base["programs"])  # the kinds' buckets differ
    assert "programs_order" in cfg["assumed"]
    # the four programs' seconds as the chip gave them: nothing pending
    seconds = cfg["measured_program_seconds"]
    assert set(seconds) - {"origin"} == set(cfg["programs"]) - {"g1dec@512"}
    assert all(0.5 < seconds[p] < 2.0 for p in cfg["programs"][:4])
    assert "PENDING" not in json.dumps(cfg)
    assert "the HARNESS's signers, not the deployment" in cfg["assumed"]["coalesce_window_s"]
    for key, value in base["guarantees"].items():
        assert cfg["guarantees"][key] == value  # none weaker
    assert cfg["guarantees"]["every_duty_of_both_kinds_broadcast_inside_its_slot"] is True
    assert cfg["guarantees"]["sync_aggregate_is_group_signature_on_the_slots_block_root"] is True
    assert set(cfg["guarantees_exercised"]) - {"not_weakened"} <= set(cfg["guarantees"])
    assert {**base["reduced"], "sync_committee_members": cfg["reduced"][
        "sync_committee_members"]} == cfg["reduced"]
    assert "512" in cfg["reduced"]["sync_committee_members"]
    assert "contributions" in cfg["assumed"]


@pytest.mark.parametrize("seed", [1, 3800000009, 2**31 + 12345])
def test_the_mix_lands_on_the_programs_the_configuration_lists(seed):
    cfg, mix = _config(), _mix()
    assert mix["fault"] == {"kind": "flip_byte", "operator": "last", "slots": "last",
                            "partials": 1, "duties": ["sync_message"]}
    assert (mix["slots"], mix["send_jitter_ms"], mix["silent_operators"]) == ("window", 30, [])
    plan = T.make_plan(cfg, mix, seed)
    T.check_programs(plan, cfg)
    assert plan.senders() == 4
    sync = next(k for k in plan.kinds if k.NAME == "sync_message")
    members = cfg["sync_committee_members"]
    assert [len(sync.members(plan, s)) for s in range(3)] == [members] * 3
    assert sync.members(plan, 0) == sync.members(plan, 7)  # the same committee every slot
    assert sync.shapes(plan) == {f"verify_rlc_dec@{4 * members}", f"step_rlc_dec@{members}"}
    # operator 4 forges its SYNC set in the last slot, and nothing else
    assert [(s, i, k.NAME) for s in range(3) for i in range(1, 5) for k in plan.kinds
            if plan.forged(s, i, 2, k.NAME)] == [(2, 4, "sync_message")]
    # 285-288 duties a run at 64 members: three slots of 31-32 + the committee
    assert 3 * (31 + members) <= sum(
        len(k.members(plan, s)) for s in range(3) for k in plan.kinds) <= 3 * (32 + members)


def test_the_bucket_of_two_kinds_sum_is_not_on_the_list():
    """`check_programs` holds the list to each kind's whole wave ALONE: a
    flush of the two kinds' sum has no program there (at the size held the
    sum is 380-384 lanes and 95-96 rows: buckets 512 and 128), which is why
    the coalescer may never make one — on the chip it would compile inside
    a slot and end the run."""
    cfg = _config()
    plan = T.make_plan(cfg, _mix(), 7)
    each = [kind.shapes(plan) for kind in plan.kinds]
    assert set().union(*each) == set(cfg["programs"]) - {"g1dec@512"}
    lanes = 4 * (32 + cfg["sync_committee_members"])
    rows = 32 + cfg["sync_committee_members"]
    merged = {f"verify_rlc_dec@{T.bucket_lanes(lanes)}", f"step_rlc_dec@{T.bucket_lanes(rows)}"}
    assert not merged & set(cfg["programs"])
    # a kind the configuration has no size for is refused before boot
    with pytest.raises(KeyError, match="sync_committee_members"):
        base = _config("dv-3of4-1k")
        T.check_programs(T.make_plan(base, _mix(), 7), base)


# -- the readers -----------------------------------------------------------------


def _reader(name):
    man = M.load_manifest(REPO)
    (metric,) = [m for m in M.load_cell(REPO, CELL, man).per_layer if m.name == name]
    return M.load_reader(REPO, man, metric.reader), metric.params


def _flush(at, kinds, device, verify_jobs=4):
    return (at, types.SimpleNamespace(duty_types=kinds, device_span=device,
                                      verify_jobs=verify_jobs))


def test_a_programs_seconds_are_given_to_the_kind_whose_flush_dispatched_it():
    from benchmark.serve import RunData

    run = RunData(window=(1000.0, 1036.0))
    run.flushes = [
        _flush(1005.8, ("attester",), (1005.0, 1005.8)),
        _flush(1008.0, ("sync_message",), (1005.9, 1008.0)),
        _flush(1009.1, ("attester",), (1008.0, 1009.1), verify_jobs=0),
        _flush(1012.0, ("sync_message",), (1009.1, 1012.0), verify_jobs=0),
        _flush(1020.0, ("sync_message",), (1017.9, 1020.0)),
        _flush(990.0, ("sync_message",), (985.0, 990.0)),  # before the window
    ]
    run.programs = [  # (family, seconds, lanes, end)
        ("verify_rlc_dec", 0.7, 128, 1005.75), ("verify_rlc_dec", 2.0, 512, 1007.95),
        ("step_rlc_dec", 1.0, 32, 1009.05), ("step_rlc_dec", 2.8, 128, 1011.95),
        ("verify_rlc_dec", 2.1, 512, 1020.0), ("verify_rlc_dec", 5.0, 512, 990.0),
    ]
    read, params = _reader("program_s.verify.sync_message")
    assert read(run, **params) == pytest.approx(2.05)  # median of 2.0, 2.1
    read, params = _reader("program_s.recombine.sync_message")
    assert read(run, **params) == pytest.approx(2.8)
    assert read(run, family="step", duty_type="attester") == pytest.approx(1.0)
    assert read(run, family="step", duty_type="proposer") is None
    # the frozen readers beside them are medians over BOTH kinds' programs
    both, params = M.load_reader(REPO, M.load_manifest(REPO), "program_seconds"), {
        "family": "verify"}
    assert both(run, **params) == pytest.approx(2.0)


def test_kinds_per_flush_is_the_most_any_flush_held():
    from benchmark.serve import RunData

    read, params = _reader("kinds_per_flush")
    run = RunData(window=(1000.0, 1036.0))
    run.flushes = [_flush(1005.0, ("attester",), None), _flush(1006.0, ("sync_message",), None),
                   _flush(1007.0, (), None)]
    assert read(run, **params) == 1.0
    run.flushes.append(_flush(1019.0, ("attester", "sync_message"), None))
    assert read(run, **params) == 2.0
    run.flushes.append(_flush(990.0, ("a", "b", "c"), None))  # before the window
    assert read(run, **params) == 2.0


def test_lane_order_flips_counts_the_slots_that_began_with_another_kind():
    from benchmark.serve import RunData

    read, params = _reader("lane_order_flips")
    run = RunData(window=(1000.0, 1036.0), slots=[7, 8, 9])
    a, s = ("attester",), ("sync_message",)
    run.flushes = [
        _flush(1005.8, a, (1005.0, 1005.8)), _flush(1007.0, s, (1005.8, 1007.0)),
        _flush(1008.0, a, (1007.0, 1008.0), verify_jobs=0),
        # slot 2: reported in another order than dispatched
        _flush(1019.0, s, (1017.8, 1019.0)), _flush(1017.8, a, (1017.0, 1017.8)),
        _flush(1029.8, a, (1029.0, 1029.8)), _flush(1031.0, s, (1029.8, 1031.0)),
        _flush(990.0, s, (989.0, 990.0)),  # before the window
    ]
    assert read(run, **params) == 0.0
    run.flushes[3], run.flushes[4] = (  # slot 2: the sync wave took the device first
        _flush(1018.2, s, (1017.0, 1018.2)), _flush(1019.0, a, (1018.2, 1019.0)))
    assert read(run, **params) == 1.0
    run.flushes[0] = _flush(1007.8, a, (1007.0, 1007.8))  # slot 1 too: slot 1 IS the rule
    assert read(run, **params) == 1.0  # now slot 3 is the odd one
    # a program from before the field: nothing to read, the metric left out
    run.flushes = [(at, types.SimpleNamespace(device_span=f.device_span, verify_jobs=4))
                   for at, f in run.flushes]
    assert read(run, **params) is None


def test_lane_yield_is_the_median_slots_yielded_seconds():
    from benchmark.serve import RunData

    read, params = _reader("lane_yield_s")
    run = RunData(window=(1000.0, 1036.0), slots=[7, 8, 9])

    def flush(at, yielded):
        return (at, types.SimpleNamespace(turn_yielded_s=yielded))

    run.flushes = [flush(1005.8, 0.0), flush(1007.0, 0.25), flush(1008.0, 0.0),
                   flush(1017.8, 0.0), flush(1019.0, 0.0),
                   flush(1029.8, 0.0), flush(1031.0, 0.125), flush(1031.5, 0.125),
                   flush(990.0, 9.0)]  # before the window
    assert read(run, **params) == pytest.approx(0.25)  # 0.25, 0.0, 0.25
    run.flushes = [flush(at, 0.0) for at, _f in run.flushes]
    assert read(run, **params) == 0.0  # nothing yielded: a number, not a gap
    run.flushes = [(at, types.SimpleNamespace()) for at, _f in run.flushes]
    assert read(run, **params) is None


def test_submits_per_wave_counts_a_kinds_spans_slot_by_slot(monkeypatch):
    from benchmark import nodespans
    from benchmark.serve import RunData

    read, params = _reader("vapi_submits_per_wave.sync_message")
    run = RunData(window=(1000.0, 1036.0), slots=[7, 8, 9])

    def span(at, duty_type, **attrs):
        return types.SimpleNamespace(name="vapi.submit", start=at, end=at + 1.0,
                                     attrs={"duty_type": duty_type, **attrs})

    one_set = [span(1004.1, "sync_message"), span(1004.2, "attester"),
               span(1016.1, "sync_message"), span(1016.1, "sync_message", shared=True),
               span(1028.1, "sync_message"), span(990.0, "sync_message")]
    monkeypatch.setattr(nodespans, "node_spans", lambda: one_set)
    assert read(run, **params) == 1.0
    # the program before ISSUE 39: a submission a message
    each = [span(1004.1 + i / 100, "sync_message") for i in range(128)] + one_set[2:]
    monkeypatch.setattr(nodespans, "node_spans", lambda: each)
    assert read(run, **params) == 1.0  # median of 128, 1, 1
    monkeypatch.setattr(nodespans, "node_spans", lambda: each + [
        span(1016.2 + i / 100, "sync_message") for i in range(127)])
    assert read(run, **params) == 128.0


# -- the rehearsals: the cell's whole control flow on the CPU ------------------------


OWN = {"attester": ("verify_rlc_dec@16", "step_rlc_dec@4"),
       "sync_message": ("verify_rlc_dec@32", "step_rlc_dec@8")}


def _rehearse(*argv):
    """The rehearsal runs on the wall clock: on a loaded CPU a set can trail
    its wave past its window's timer and flush alone (tests/test_node_down.py).
    That is not what these tests are about, so such a run is made again,
    twice at most."""
    for _attempt in range(3):
        proc = subprocess.run(
            [sys.executable, str(REPO / "benchmark/tests/rehearse_twokinds.py"), *argv],
            capture_output=True, text=True, timeout=240, cwd=str(REPO))
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        seen = json.loads(lines[-1])
        if all(f["window_closed_by"] == "complete" for f in seen["flushes"]):
            break
    return types.SimpleNamespace(line=json.loads(lines[-2]), seen=seen, stderr=proc.stderr)


@pytest.fixture(scope="module")
def honest():
    return _rehearse()


@pytest.fixture(scope="module")
def forged():
    return _rehearse("--forged")


def _four_flushes_a_slot(flushes):
    """In every slot exactly four flushes, one a kind and family, each on
    its kind's own bucket, each closed `complete`, none holding two kinds."""
    for slot in (0, 1):
        mine = [f for f in flushes if f["slot"] == slot]
        assert sorted((f["duty_types"][0], f["program"]) for f in mine) == sorted(
            (kind, program) for kind, programs in OWN.items() for program in programs)
        for f in mine:
            assert len(f["duty_types"]) == 1 and f["window_closed_by"] == "complete"
            assert f["window_parts"] in (1, 2)  # 2: both kinds whole on one wake
            if f["verify_jobs"]:
                assert (f["verify_jobs"], f["recombine_jobs"]) == (4, 0)
                assert (f["sets_expected"], f["sets_seen"], f["sets_awaited"]) == (4, 4, 4)
            else:
                assert (f["verify_jobs"], f["recombine_jobs"]) == (0, 1)
        # the device's order is the lane's rule (earliest deadline, then
        # fewest lanes: the attester wave), not the order in which the
        # waves' last sets came: the sync wave's program is first only
        # where no attester set was in when its own wave closed — nothing
        # armed, nothing to yield to, and a free device does not wait
        verify = {f["duty_types"][0]: f for f in mine if f["verify_jobs"]}
        att, sync = verify["attester"], verify["sync_message"]
        if sync["device_from_s"] < att["device_from_s"]:
            assert att["window_s"][0] > sync["window_s"][1] and not sync["turn_yielded_s"]
        else:
            assert not att["turn_yielded_s"]
            assert sync["turn_yielded_to"] in ("", "attester")
            assert bool(sync["turn_yielded_s"]) == bool(sync["turn_yielded_to"])


def test_the_two_kind_rehearsal_ends_correct_on_each_kinds_own_buckets(honest):
    line = honest.line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 17
    assert all(c["value"] == 0 for c in line["checks"].values())
    _four_flushes_a_slot(honest.seen["flushes"])
    assert all(f["sets_invalid"] == 0 for f in honest.seen["flushes"])


def test_the_rehearsals_traced_line_carries_the_cells_new_metrics(honest):
    metrics = honest.line["metrics"]
    assert metrics["kinds_per_flush"] == {"value": 1.0, "unit": "count"}
    assert metrics["vapi_submits_per_wave.sync_message"] == {"value": 1.0, "unit": "count"}
    assert 0 < metrics["duty_p50_s.attester"]["value"] < 3.0
    assert 0 < metrics["duty_p50_s.sync_message"]["value"] < 3.0
    assert not set(LEFT_OUT) & set(metrics)
    # the order on the device, read as the flushes themselves say it
    first = {}
    for f in sorted(honest.seen["flushes"], key=lambda f: f["device_from_s"]):
        if f["verify_jobs"]:
            first.setdefault(f["slot"], f["duty_types"])
    assert metrics["lane_order_flips"] == {
        "value": float(first[0] != first[1]), "unit": "count"}
    yielded = [sum(f["turn_yielded_s"] for f in honest.seen["flushes"] if f["slot"] == k)
               for k in (0, 1)]
    assert metrics["lane_yield_s"]["value"] == pytest.approx(sum(yielded) / 2)
    # no program on the CPU's sleeping plane: the two program metrics are
    # left out of the line, as a reader that finds nothing must leave them
    assert "program_s.verify.sync_message" not in metrics


def test_the_vc_s_sync_messages_are_one_submission_a_slot(honest):
    spans = [s for s in honest.seen["spans"] if s["name"] == "vapi.submit"]
    by_kind = {}
    for s in spans:
        by_kind.setdefault(s["attrs"]["duty_type"], []).append(s["attrs"]["count"])
    assert by_kind["sync_message"] == [5, 5] and len(by_kind["attester"]) == 2
    windows = [s["attrs"] for s in honest.seen["spans"]
               if s["name"] == "cryptoplane.window" and not s["attrs"].get("shared")]
    assert sorted(w["duty_types"] for w in windows) == ["attester"] * 4 + ["sync_message"] * 4
    assert all(w["parts"] in (1, 2) and w["closed_by"] == "complete" for w in windows)
    devices = [s["attrs"] for s in honest.seen["spans"]
               if s["name"] == "cryptoplane.device" and not s["attrs"].get("shared")]
    assert sorted(x["duty_types"] for x in devices) == sorted(w["duty_types"] for w in windows)


def test_a_forged_sync_set_is_refused_once_and_its_attester_set_passes(forged):
    line = forged.line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 17
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert "forged_sets_not_rejected 0 limit 0 ok" in forged.stderr
    flushes = forged.seen["flushes"]
    _four_flushes_a_slot(flushes)
    # refused once: the last slot's sync set, whole, and nothing else (the
    # sleeping plane passes every lane; the verdict is the C++ engine's,
    # beside it: benchmark/tests/planepatch.Hinted)
    (bad,) = [s for s in forged.seen["spans"]
              if s["name"] == "parsigex.verify" and s["attrs"].get("ok") is False]
    assert bad["attrs"]["pubkeys"] == 5
    traces = {s["trace_id"]: s["attrs"]["duty"] for s in forged.seen["spans"]
              if "duty" in s["attrs"]}
    assert traces[bad["trace_id"]].endswith("/sync_message")
    # every sync duty of that slot still completed, from the three honest sets
    aggregates = [s["attrs"] for s in forged.seen["spans"] if s["name"] == "sigagg.aggregate"]
    assert sorted(a["partials"] for a in aggregates) == [3, 3, 3, 3]


# -- a peer's two sets of a slot, on one connection --------------------------------


class _Kind:  # what the adapter reads of a core/types.Duty
    def __init__(self, type):
        self.type = type


class _Mesh:  # what the adapter asks of a P2PNode
    def __init__(self):
        self.tasks, self.dropped = [], []

    def register_handler(self, protocol, handler):
        self.handler = handler

    def detach(self, coro):
        self.tasks.append(asyncio.ensure_future(coro))
        return self.tasks[-1]

    def drop_frame(self, peer_idx, err):
        self.dropped.append((peer_idx, type(err)))


class _Receiver:  # ParSigEx: an attester set's receive ends with its wave's flush
    def __init__(self):
        self.started, self.done, self.flushed = [], [], asyncio.Event()

    async def receive(self, duty, signed_set, tctx=None, sender=None):
        self.started.append((duty.type, signed_set["n"], sender))
        if duty.type == "attester":
            await self.flushed.wait()  # its wave's verify program
        if duty.type == "bad":
            raise ValueError("a handler bug drops its frame, nothing else")
        self.done.append((duty.type, signed_set["n"]))


def _frame(kind, n=0):
    return {"duty": _Kind(kind), "set": {"n": n}}


def test_a_peers_second_set_is_received_beside_its_first_sets_flush():
    """`P2PNode._recv_loop` awaits a handler before it reads the
    connection's next frame, and `ParSigEx.receive` ends when the set's
    verify flush does: the adapter hands it to a task of the node's, so a
    peer's sync set is received while its attester set's wave is still on
    the device (two peers sending in opposite orders used to hold each
    kind's wave short of the other's set until a timer fired)."""
    from charon_tpu.p2p.adapters import TcpParSigTransport

    async def main():
        node, local = _Mesh(), _Receiver()
        transport = TcpParSigTransport(node)
        transport.attach(local)
        for kind in ("attester", "bad", "sync_message"):
            assert await node.handler(2, _frame(kind)) is None  # at once
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert local.started == [("attester", 0, 3), ("bad", 0, 3), ("sync_message", 0, 3)]
        assert local.done == [("sync_message", 0)]  # not behind the attester set's flush
        assert node.dropped == [(2, ValueError)]  # the bad frame's error: the node's drop
        local.flushed.set()
        await asyncio.gather(*node.tasks)
        assert local.done == [("sync_message", 0), ("attester", 0)]
        await asyncio.sleep(0)  # the last task's done callback
        assert transport._in_flight == {}

    asyncio.run(main())


def test_a_peer_has_one_receive_in_flight_for_a_duty_type():
    """What awaiting the handler gave the connection, kept per duty type:
    a peer's second set of a type holds its read loop until the first has
    ended, so a peer has at most one set of each type in the decode pool
    and the coalescer, and its sets of a type are received in order."""
    from charon_tpu.p2p.adapters import TcpParSigTransport

    async def main():
        node, local = _Mesh(), _Receiver()
        transport = TcpParSigTransport(node)
        transport.attach(local)

        async def read_loop():  # one connection: a frame's handler, then the next frame
            for n in range(1, 6):
                await node.handler(2, _frame("attester", n))
            await node.handler(2, _frame("sync_message", 6))

        conn = asyncio.ensure_future(read_loop())
        other = asyncio.ensure_future(node.handler(1, _frame("attester", 7)))
        for _ in range(10):
            await asyncio.sleep(0)
        # the peer's first set is on the device; its second waits in the read loop,
        # whatever the peer has sent behind it; another peer's set is not held
        assert local.started == [("attester", 1, 3), ("attester", 7, 2)]
        assert not conn.done() and other.done()
        assert len(transport._in_flight) == 2 and len(node.tasks) == 2
        local.flushed.set()
        await conn
        await asyncio.gather(*node.tasks)
        assert [n for _k, n, s in local.started if s == 3] == [1, 2, 3, 4, 5, 6]
        await asyncio.sleep(0)  # the last tasks' done callbacks
        assert transport._in_flight == {}

    asyncio.run(main())


def test_a_malformed_set_in_a_detached_receive_is_its_peers_strike():
    """An error raised in `receive` after the read loop has gone on reaches
    what the read loop would have done with it: a `CodecError` is counted
    and strikes the peer towards its quarantine, anything else is logged."""
    from charon_tpu.app import k1util
    from charon_tpu.p2p import codec
    from charon_tpu.p2p.adapters import TcpParSigTransport
    from charon_tpu.p2p.transport import P2PNode, PeerSpec

    keys = [k1util.generate_private_key() for _ in range(2)]
    specs = [PeerSpec(index=i, pubkey=k1util.public_key_to_bytes(k.public_key()),
                      host="127.0.0.1", port=1 + i) for i, k in enumerate(keys)]

    class Malformed:
        async def receive(self, duty, signed_set, tctx=None, sender=None):
            if duty.type == "attester":
                raise codec.CodecError("a set that does not decode")
            raise RuntimeError("a bug")

    async def main():
        node = P2PNode(0, keys[0], specs, b"\x11" * 32)
        transport = TcpParSigTransport(node)
        transport.attach(Malformed())
        handler = node._handlers["parsigex/2.0.0"]
        strikes = node._quarantine.strikes
        for n in range(strikes):
            await handler(1, _frame("attester", n))
            await handler(1, _frame("sync_message", n))
        await asyncio.gather(*node._recv_tasks)
        muted = node.peer_quarantined(1)
        await node.stop()
        return node.codec_dropped, muted, strikes

    dropped, muted, strikes = asyncio.run(main())
    assert dropped == strikes and muted


def test_a_detached_handler_is_the_nodes_own_task_and_stops_with_it():
    from charon_tpu.app import k1util
    from charon_tpu.p2p.transport import P2PNode, PeerSpec

    key = k1util.generate_private_key()
    spec = PeerSpec(index=0, pubkey=k1util.public_key_to_bytes(key.public_key()),
                    host="127.0.0.1", port=1)

    async def main():
        node = P2PNode(0, key, [spec], b"\x11" * 32)
        state = []

        async def tail():
            try:
                await asyncio.sleep(3600)
            except asyncio.CancelledError:
                state.append("cancelled")
                raise

        node.detach(tail())
        await asyncio.sleep(0)
        (task,) = node._recv_tasks
        await node.stop()
        await asyncio.gather(task, return_exceptions=True)
        return state, task.cancelled()

    assert asyncio.run(main()) == (["cancelled"], True)


# -- what a flush says of its kind ---------------------------------------------------


def test_the_bridge_says_the_flushs_kind_on_window_flush_and_device():
    from tests.test_tracer import _flush_stats

    t = tracer.Tracer()
    bridge = tracer.plane_span_bridge(t)
    bridge(_flush_stats(duty_types=("sync_message",), window_parts=2, turn_yielded_s=0.21,
                        turn_yielded_to="attester", parents=(("a" * 32, "b" * 16),)))
    bridge(_flush_stats(parents=(("c" * 32, "d" * 16),)))  # jobs that named no duty
    spans = {(s["trace_id"][0], s["name"]): s["attrs"] for s in t.dump()}
    for name in ("cryptoplane.window", "cryptoplane.flush", "cryptoplane.device"):
        assert spans["a", name]["duty_types"] == "sync_message"
        assert "duty_types" not in spans["c", name]
    assert spans["a", "cryptoplane.flush"]["yielded"] == 0.21  # let a more urgent kind go first
    assert spans["a", "cryptoplane.flush"]["yielded_to"] == "attester"  # and which
    assert not {"yielded", "yielded_to"} & set(spans["c", "cryptoplane.flush"])
    assert spans["a", "cryptoplane.window"]["parts"] == 2
    assert spans["c", "cryptoplane.window"]["parts"] == 1
    assert "duty_types" not in spans["a", "cryptoplane.pack"]


def test_the_node_counts_flushes_lanes_and_seconds_by_kind():
    from charon_tpu.app.metrics import ClusterMetrics

    text = (REPO / "charon_tpu/app/run.py").read_text()
    for family in ("plane_flushes, kind", "plane_lanes, kind", "plane_flush_seconds, kind",
                   "plane_window_parts", "plane_lane_yielded, kind, s.turn_yielded_to"):
        assert f"metrics.{family}" in text, family
    m = ClusterMetrics("hash", "name", "peer")
    m.labels(m.plane_flushes, "sync_message").inc()
    m.labels(m.plane_lanes, "sync_message").inc(512)
    m.labels(m.plane_flush_seconds, "attester").observe(0.7)
    m.labels(m.plane_window_parts).inc()
    m.labels(m.plane_lane_yielded, "sync_message", "attester").inc(0.014)
    out = m.render().decode() if isinstance(m.render(), bytes) else m.render()
    assert 'duty_type="sync_message"' in out and "tpu_plane_window_parts_total" in out
    assert 'tpu_plane_flush_seconds_sum{' in out and 'duty_type="attester"' in out
    assert "tpu_plane_lane_yielded_seconds_total{" in out and 'to="attester"' in out
    docs = (REPO / "docs/metrics.md").read_text()
    for name in ("tpu_plane_window_parts_total", "`duty_types`", "`parts`",
                 "tpu_plane_lane_yielded_seconds_total", "`yielded_to`"):
        assert name in docs, name


@pytest.mark.parametrize("key,kind", [
    ((Duty(5, DutyType.SYNC_MESSAGE), frozenset({"v"})), "sync_message"),  # a verifier's
    (Duty(5, DutyType.ATTESTER), "attester"),  # SigAgg's
    (("tenant-a", (Duty(5, DutyType.ATTESTER), frozenset())), "attester"),  # through a tenant
    (("tenant-a", "duty-5"), ""),  # tools, tests: no duty in it
    ("duty-5", ""),
])
def test_a_jobs_kind_is_the_type_of_the_duty_in_its_wave_key(key, kind):
    from charon_tpu.core.cryptoplane import _kind_of

    assert _kind_of(((key, 4),)) == kind
    assert _kind_of(None) == _kind_of(()) == ""
