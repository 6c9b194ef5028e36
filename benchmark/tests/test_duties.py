"""A duty kind is a file (ISSUE 37): what `benchmark/duties/attester.py` gives
is what the harness's attester-literal code gave before the move, value for
value; `make_plan` takes a mix's kinds by name; the second kind,
`duties/sync_message.py`, runs the whole control flow on the CPU beside the
attester; the plain reference of its signing root; the two readers that took
a parameter; and the forged cell's metrics after PR 36 emptied five of them.

    python -m pytest benchmark/tests/test_duties.py -q -p no:cacheprovider
"""

from __future__ import annotations

import functools
import hashlib
import json
import re
import subprocess
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import check, manifest as M, reference as R, reference_sync as RS  # noqa: E402
from benchmark import traffic as T  # noqa: E402
from benchmark.serve import DutyRecord, RunData  # noqa: E402
from benchmark.tests import helpers  # noqa: E402

FORGED_CELL = "dv-3of4-1k-byz.attest-forged"
RETIRED = ("program_s.attribute", "attribute_s", "program_s.verify_rlc",
           "device_busy_s.verify_rlc", "lanes_invalid_per_wave")

# -- the attester, moved: byte for byte what the parent's code gave -----------

# benchmark/tests/data/parent_attester.json was written from the PARENT of
# PR 37 (commit b6bf78e: `traffic.make_plan`, `Plan.members` / `.flush_shapes`
# / `.attestation_fields` / `.jitter` / `.forged`, `serve.open_window`'s
# records at ATTESTER_OFFSET, `reference.attestation_signing_root`) for every
# cell of the manifest and four seeds; the long lists as SHA-256 of their JSON.
PARENT = json.loads((REPO / "benchmark/tests/data/parent_attester.json").read_text())


def _digest(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, separators=(",", ":")).encode()).hexdigest()


def _hexed(fields):
    return [f.hex() if isinstance(f, bytes) else f for f in fields]


@pytest.mark.parametrize("case", sorted(PARENT["cases"]))
def test_the_attester_module_gives_what_the_parents_code_gave(case):
    want = PARENT["cases"][case]
    name, seed = case.rsplit("/", 1)
    cell = M.load_cell(REPO, name)
    plan = T.make_plan(cell.config, cell.traffic, int(seed))
    (attester,) = plan.kinds
    spe, slots = plan.slots_per_epoch, PARENT["slots"]
    chain = tuple(bytes.fromhex(c) for c in PARENT["chain"])
    assert _digest({
        "seed": plan.seed, "operators": plan.operators, "threshold": plan.threshold,
        "validators": plan.validators, "slots_per_epoch": spe,
        "slot_duration": plan.slot_duration, "duties": list(plan.duties),
        "jitter_s": plan.jitter_s,
        "fault": [plan.fault.kind, plan.fault.operator, plan.fault.slots, plan.fault.partials],
        "silent": list(plan.silent), "rank": list(plan.rank)}) == want["plan"]
    # the shapes: the parent listed the per-lane program under `wrong_key`;
    # since PR 36 no whole wave reaches it, and PR 37 took it off the list
    assert sorted(plan.flush_shapes()) == [
        s for s in want["shapes"] if not s.startswith("verify_dec@")]
    assert plan.senders() == want["senders"]
    assert _digest([attester.members(plan, p) for p in range(spe)]) == want["members"]
    assert [len(attester.members(plan, p)) for p in range(spe)] == want["duties_in"]
    assert _digest([[repr(plan.jitter(i, s)) for s in slots]
                    for i in range(2, plan.operators + 1)]) == want["jitter"]
    assert [[plan.forged(s, i, slots[-1], attester.NAME) for s in slots]
            for i in range(1, plan.operators + 1)] == want["forged"]
    records, expected = [], []
    for slot in slots:  # serve.open_window's loop, on a fixed genesis
        due = (PARENT["genesis"] + slot * plan.slot_duration) + attester.OFFSET * plan.slot_duration
        for vidx in attester.members(plan, slot):
            rec = DutyRecord(attester.NAME, slot, vidx, "0x", due)
            records.append([rec.slot, rec.vidx, repr(rec.due)])
            fields, root = attester.expected(plan, rec, chain)
            expected.append([_hexed(fields), root.hex()])
    assert len(records) == want["records"]["count"] and records[0] == want["records"]["first"]
    assert _digest(records) == want["records"]["all"]
    assert expected[0] == want["expected"]["first"]
    assert _digest(expected) == want["expected"]["all"]


@pytest.mark.parametrize("name", ["traffic.py", "serve.py", "check.py"])
def test_the_generic_files_name_no_kind_of_duty(name):
    text = (REPO / "benchmark" / name).read_text()
    for token in ("DutyType.ATTESTER", "ATTESTER_OFFSET", "attestation_fields",
                  "attestation_signing_root", "sign_attestations", "submit_attestation",
                  "sync_message", "SYNC_MESSAGE", "registration", "REGISTRATION",
                  "submit_registration", "register_validators"):
        assert token not in text, token


# -- make_plan takes a mix's kinds by name ------------------------------------


def _rehearsal_config(**more):
    return dict(helpers.REHEARSAL, **more)


def _mix(duties, **more):
    return dict({"name": "m", "duties": duties, "send_jitter_ms": 0,
                 "fault": {"kind": "none"}, "silent_operators": []}, **more)


def test_make_plan_refuses_a_kind_that_has_no_file_and_nothing_else_about_it():
    with pytest.raises(T.TrafficError, match="no duty kind 'proposer'"):
        T.make_plan(_rehearsal_config(), _mix(["attester", "proposer"]), 1)
    with pytest.raises(T.TrafficError, match="duties"):
        T.make_plan(_rehearsal_config(), _mix([]), 1)
    with pytest.raises(T.TrafficError, match="duties"):
        T.make_plan(_rehearsal_config(), _mix(["attester", "attester"]), 1)
    with pytest.raises(T.TrafficError, match="no duty kind"):
        T.make_plan(_rehearsal_config(), _mix(["../traffic"]), 1)


def test_make_plan_accepts_a_two_kind_mix_and_the_shapes_are_the_union():
    cfg = _rehearsal_config(sync_committee_members=5)
    plan = T.make_plan(cfg, _mix(["attester", "sync_message"]), 7)
    assert [k.NAME for k in plan.kinds] == ["attester", "sync_message"]
    alone = [T.make_plan(cfg, _mix([k]), 7).flush_shapes() for k in plan.duties]
    assert alone == [{"verify_rlc_dec@16", "step_rlc_dec@4"},
                     {"verify_rlc_dec@32", "step_rlc_dec@8"}]
    assert plan.flush_shapes() == alone[0] | alone[1]
    with pytest.raises(T.TrafficError, match="sync_message.*20"):
        T.check_programs(plan, cfg)  # the rehearsal's list is the attester's alone
    T.check_programs(plan, dict(cfg, programs=sorted(plan.flush_shapes()) + ["g1dec@512"]))


def test_a_fault_may_name_the_kinds_whose_sets_it_forges():
    cfg = _rehearsal_config(sync_committee_members=5)
    fault = {"kind": "flip_byte", "operator": "last", "slots": "last", "partials": 1}
    plan = T.make_plan(cfg, _mix(["attester", "sync_message"], fault=fault), 7)
    assert plan.forged(9, 4, 9, "attester") and plan.forged(9, 4, 9, "sync_message")
    plan = T.make_plan(cfg, _mix(["attester", "sync_message"],
                                 fault=dict(fault, duties=["sync_message"])), 7)
    assert not plan.forged(9, 4, 9, "attester") and plan.forged(9, 4, 9, "sync_message")
    assert not plan.forged(8, 4, 9, "sync_message") and not plan.forged(9, 3, 9, "sync_message")
    with pytest.raises(T.TrafficError, match="fault duties"):
        T.make_plan(cfg, _mix(["attester"], fault=dict(fault, duties=["sync_message"])), 7)


def test_a_kind_is_added_as_a_file_and_its_name_in_a_mix(tmp_path):
    root = helpers.make_root(tmp_path)
    bdir = root / "benchmark"
    source = (bdir / "duties" / "attester.py").read_text()
    (bdir / "duties" / "attester_late.py").write_text(
        source.replace('NAME = "attester"', 'NAME = "attester_late"')
        .replace("OFFSET = 1.0 / 3.0", "OFFSET = 0.5"))
    with pytest.raises(T.TrafficError, match="no duty kind 'attester_late'"):
        T.make_plan(_rehearsal_config(), _mix(["attester_late"]), 3)  # not beside the code
    plan = T.make_plan(_rehearsal_config(), _mix(["attester", "attester_late"]), 3, bdir)
    assert [(k.NAME, k.OFFSET) for k in plan.kinds] == [
        ("attester", 1.0 / 3.0), ("attester_late", 0.5)]
    T.check_programs(plan, _rehearsal_config())  # the same waves: the same shapes


# -- the second kind ----------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 3700000011, 2**31 + 12345])
def test_the_sync_committee_is_the_head_of_the_seeded_order_in_every_slot(seed):
    cfg = _rehearsal_config(sync_committee_members=5)
    plan = T.make_plan(cfg, _mix(["attester", "sync_message"]), seed)
    sync = plan.kinds[1]
    committee = sync.members(plan, 0)
    assert len(committee) == 5 and len(set(committee)) == 5
    assert [plan.rank[v] for v in committee] == [0, 1, 2, 3, 4]
    assert all(sync.members(plan, slot) == committee for slot in (1, 3, 4, 1000))
    # the message signs the root the slot's attesters vote for
    attester = plan.kinds[0]
    assert sync.block_root(plan, 37) == attester.fields(plan, 37, 0)[2]
    assert sync.block_root(plan, 37) != sync.block_root(plan, 38)
    msg = types.SimpleNamespace(slot=37, beacon_block_root=b"r" * 32, validator_index=9,
                                signature=b"s" * 96)
    assert sync.submitted(types.SimpleNamespace(plan=plan), msg) == (37, 9, b"s" * 96, (37, b"r" * 32, 9))


@pytest.mark.parametrize("seed", [1, 3700000011, 2**31 + 12345])
def test_the_plain_sync_signing_root_is_the_programs_ssz_root(seed):
    """Two implementations that share no code agree, on seeded block roots
    and chains: benchmark/reference_sync.py and the program's SignedData."""
    from charon_tpu.core.eth2data import SignedData, SyncCommitteeMessage
    from charon_tpu.eth2util.signing import ForkInfo

    for i in range(4):
        root = hashlib.sha256(b"block %d %d" % (seed, i)).digest()
        gvr = hashlib.sha256(b"gvr %d %d" % (seed, i)).digest()
        version = bytes([i, 0, seed % 251, 1])
        fork = ForkInfo(genesis_validators_root=gvr, fork_version=version,
                        genesis_fork_version=version)
        theirs = SignedData("sync_message", SyncCommitteeMessage(33 + i, root, 7)).signing_root(
            fork, 1)
        assert RS.sync_message_signing_root(root, version, gvr) == theirs


def test_the_plain_sync_signing_root_against_an_answer_anyone_can_recompute():
    """The all-zero block root on the all-zero chain: SigningData's two
    leaves are the root and the domain, the domain the type's four bytes
    and 28 of H(64 zero bytes) — the tree test_reference.py walks for the
    attester, under DOMAIN_SYNC_COMMITTEE."""
    assert RS.DOMAIN_SYNC_COMMITTEE == bytes.fromhex("07000000")
    domain = RS.DOMAIN_SYNC_COMMITTEE + hashlib.sha256(bytes(64)).digest()[:28]
    want = hashlib.sha256(bytes(32) + domain).digest()
    assert RS.sync_message_signing_root(bytes(32), bytes(4), bytes(32)) == want
    assert want != R.attestation_signing_root(
        (0, 0, bytes(32), 0, bytes(32), 0, bytes(32)), bytes(4), bytes(32))
    with pytest.raises(ValueError):
        RS.sync_message_signing_root(bytes(31), bytes(4), bytes(32))


def test_reference_sync_imports_the_reference_alone():
    import ast

    tree = ast.parse((REPO / "benchmark/reference_sync.py").read_text())
    imported = {n.module if isinstance(n, ast.ImportFrom) else a.name
                for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in (n.names if isinstance(n, ast.Import) else [None])}
    assert imported == {"__future__", "benchmark.reference"}


@functools.cache
def _rehearse_sync(*extra):
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/tests/rehearse_sync.py"), *extra],
        capture_output=True, text=True, timeout=240, cwd=str(REPO))
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    info = json.loads(lines[0])["info"]
    return proc.returncode, json.loads(lines[1]), info, proc.stderr


def _over_limit(line: dict) -> dict:
    return {k: c["value"] for k, c in line["checks"].items() if c["value"] > c["limit"]}


def test_a_two_kind_mix_runs_the_whole_control_flow_and_ends_correct():
    rc, line, info, err = _rehearse_sync()
    assert rc == 0, err[-3000:]
    # two slots: 4 + 3 attesters, and the committee of 5 in each
    assert line["correct"] is True and line["attempted"] == 17 and line["failed"] == 0
    assert list(line["checks"]) == list(check.PER_DUTY) + [
        "forged_sets_not_rejected", "degradation_events", "compiles_in_window"]
    assert all(c == {"value": 0, "limit": 0} for c in line["checks"].values())
    assert set(line["metrics"]) == {"duty_p50_s", "duty_p95_s", "setup_s"}
    # each kind's VC made its own round in every slot
    rounds = [name for name, _at, _took in info["vc_spans_s"]]
    assert sorted(rounds) == sorted(2 * ["vc_attestation_data", "vc_head_root"]
                                    + 4 * ["vc_sign", "http_submit"])
    assert info["forged_sets"] == {"sent": 0, "rejected": 0}


def test_a_forged_sync_message_set_is_rejected_exactly_once():
    rc, line, info, err = _rehearse_sync("--forged")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and line["attempted"] == 17 and line["failed"] == 0
    assert info["forged_sets"] == {"sent": 1, "rejected": 1}
    assert "forged_sets_not_rejected 0 limit 0 ok" in err


def test_a_node_that_trusts_its_peers_sync_sets_comes_out_not_correct():
    """The timed path broken at its entry (helpers.trusted_peers): the forged
    sync-message set is never rejected. What becomes of its one flipped
    partial is a race the node does not decide, as for the attester in
    test_benchmark.py: where it is among the first t of its validator, the
    node's own check of the group signature refuses the aggregates made
    beside it — the VC's messages reach the node one request each, so from
    one to all five of the last slot's committee go missing."""
    rc, line, _info, err = _rehearse_sync("--forged", "--patch", "trusted_peers")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    over = _over_limit(line)
    assert over.pop("forged_sets_not_rejected") == 1
    assert set(over) <= {"duties_missing"} and over.get("duties_missing", 0) <= 5
    assert line["failed"] == over.get("duties_missing", 0)


# -- check.compare and the readers, by kind -----------------------------------


def test_compare_reads_every_record_through_its_kind():
    cfg = _rehearsal_config(sync_committee_members=2)
    plan = T.make_plan(cfg, _mix(["attester", "sync_message"]), 11)
    chain = (bytes(4), hashlib.sha256(b"gvr").digest())
    secret = R.seeded_scalar("duties-test", 1).to_bytes(32, "big")
    pubkey = "0x" + R.secret_to_public_key(secret).hex()
    cluster = types.SimpleNamespace(group_secrets={pubkey: secret})
    run, slot = RunData(), 5
    for kind in plan.kinds:
        for vidx in kind.members(plan, slot)[:2]:
            rec = DutyRecord(kind.NAME, slot, vidx, pubkey, 0.0)
            rec.data, rec.root = kind.expected(plan, rec, chain)
            rec.done, rec.broadcasts, rec.signature = 1.0, 1, R.sign(secret, rec.root)
            run.duties.append(rec)
    assert {d.kind for d in run.duties} == {"attester", "sync_message"}
    sound = check.compare(run, cluster, plan, chain, {}, 0, 0, 0)
    assert check.verdict(sound) and all(c["value"] == 0 for c in sound.values())
    # a sync message on another head root: its data and its aggregate differ
    last = run.duties[-1]
    last.data = (last.data[0], b"x" * 32, last.data[2])
    last.signature = R.sign(secret, b"y" * 32)
    broken = check.compare(run, cluster, plan, chain, {}, 0, 0, 0)
    assert _over_limit({"checks": broken}) == {
        "attestation_data_differ": 1, "aggregates_differ": 1}
    # the records of one slot and validator under two kinds are two records
    assert len({(d.kind, d.slot, d.vidx) for d in run.duties}) == len(run.duties)


def test_the_latency_reader_takes_one_kinds_sample():
    read = M.load_reader(REPO, M.load_manifest(REPO), "duty_latency")
    run = RunData(gave_up=130.0)
    for kind, took in (("attester", 2.0), ("attester", 3.0), ("sync_message", 5.0),
                       ("sync_message", None)):
        rec = DutyRecord(kind, 1, len(run.duties), "0x", 100.0)
        rec.done = None if took is None else 100.0 + took
        run.duties.append(rec)
    assert read(run, q=50) == 3.0 and read(run, q=95) == 30.0  # all duties, as before
    assert read(run, q=50, kind="attester") == 2.0 and read(run, q=95, kind="attester") == 3.0
    assert read(run, q=50, kind="sync_message") == 5.0
    assert read(run, q=50, kind="proposer") is None  # nothing to read


def test_sets_invalid_per_wave_reads_the_refused_sets_of_a_waves_verify_flushes():
    man = M.load_manifest(REPO)
    (metric,) = [m for m in M.load_cell(REPO, FORGED_CELL, man).per_layer
                 if m.name == "sets_invalid_per_wave"]
    assert (metric.reader, metric.params) == ("flush_attribution", {"field": "sets_invalid"})
    read = M.load_reader(REPO, man, metric.reader)
    run = RunData(window=(1000.0, 1036.0))

    def flush(at, verify_jobs, sets_invalid, lanes_invalid):
        return (at, types.SimpleNamespace(verify_jobs=verify_jobs, sets_invalid=sets_invalid,
                                          lanes_invalid=lanes_invalid))

    run.flushes = [flush(1005.0, 4, 1, 31), flush(1007.0, 0, 0, 0),  # verify, recombine
                   flush(1017.0, 2, 1, 32), flush(1017.5, 2, 2, 64), flush(1019.0, 0, 0, 0),
                   flush(1029.0, 4, 0, 0), flush(1031.0, 0, 0, 0),  # an honest wave
                   flush(990.0, 4, 9, 99)]  # before the window
    assert read(run, **metric.params) == 1.0  # median of 1, 3, 0
    assert read(run) == 31.0  # the lanes (31, 96, 0), as the retired metric read them
    run.flushes = [(1005.0, types.SimpleNamespace(verify_jobs=4, lanes=128))]  # an older program
    assert read(run, **metric.params) is None


# -- the forged cell after PR 36 emptied five of its metrics ------------------


@pytest.mark.parametrize("name", RETIRED)
def test_a_retired_metric_left_the_manifest_and_took_its_file(name):
    man = M.load_manifest(REPO)
    assert name not in {m["name"] for m in man["per_layer"]}
    assert not (REPO / "benchmark/metrics" / f"{name}.json").exists()


def test_the_forged_cell_reports_the_verify_program_like_the_other_three():
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    # the manifest's size is no longer pinned here (20 metrics and 4 cells when
    # PR 37 wrote this; later PRs add cells and metrics): the cell's own are
    names = [m.name for m in M.load_cell(REPO, FORGED_CELL, man).per_layer]
    assert len(names) == 18 and names[-1] == "sets_invalid_per_wave"
    assert {"program_s.verify", "device_busy_s.verify"} <= set(names)
    assert not {"window_wait_s.verify", "sets_short_per_wave"} & set(names)
    for entry in man["per_layer"]:  # every list explicit: a new cell joins the ones it reports
        assert entry["workloads"] and set(entry["workloads"]) <= {
            w["name"] for w in man["workloads"]}
    (workload,) = [w for w in man["workloads"] if w["name"] == FORGED_CELL]
    assert "one dispatch" in workload["why"] and len(workload["why"]) <= 200


def test_the_forged_configuration_lists_dv_3of4_1ks_programs():
    cfg = json.loads((REPO / "benchmark/configs/dv-3of4-1k-byz.json").read_text())
    base = json.loads((REPO / "benchmark/configs/dv-3of4-1k.json").read_text())
    assert cfg["programs"] == base["programs"] == [
        "verify_rlc_dec@128", "step_rlc_dec@32", "g1dec@512"]
    for key, value in base["guarantees"].items():
        assert cfg["guarantees"][key] == value  # none weaker
    assert cfg["guarantees"]["every_duty_completes_without_the_forgers_set"] is True
    assert cfg["guarantees"]["honest_sets_of_a_flush_with_a_refused_set_pass"] is True
    assert set(cfg["guarantees_exercised"]) - {"not_weakened"} <= set(cfg["guarantees"])
    assert not re.search(r"forces the per-lane", json.dumps(cfg))
    mix = json.loads((REPO / "benchmark/mixes/attest-forged.json").read_text())
    for seed in (1, 3500000009, 2**31 + 12345):
        T.check_programs(T.make_plan(cfg, mix, seed), cfg)
        T.check_programs(T.make_plan(base, mix, seed), base)  # no third program to refuse it
