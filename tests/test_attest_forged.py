"""One Byzantine operator (ISSUE 35: `dv-3of4-1k-byz.attest-forged`): what a
flush says when its RLC verify tier fails and the plane falls to its
per-lane program — `FlushStats.attributed` / `lanes_invalid` / `sets_invalid`
/ `attribute_span`, the `cryptoplane.attribute` span, the coalescer's
counters (the point path, which these fakes are; on a node that decodes on
the device the RLC program answers per set since PR 36 and the flush says
`set_resolved`: tests/test_set_verdicts.py); that through the node's own
submitters (ValidatorAPI, ParSigEx's verifier, ParSigDB, SigAgg) a
well-formed forged partial fails ITS set and no other, lane for lane as the
plain reference (benchmark/reference_verify.py) says, wherever in the set it
sits, and every duty is made from exactly t partials without the forger's;
that the configuration (`dv-3of4-1k`'s programs as they stand since PR 37),
the mix and the cell's one metric of its own (`sets_invalid_per_wave`) pass
the harness's pre-boot checks; and one rehearsal of the cell's control flow
on the CPU (benchmark/tests/rehearse_forged.py). Restated in PR 39 for what
PRs 36-37 retired: `verify_dec@128`, `program_s.verify_rlc`,
`program_s.attribute`, `device_busy_s.verify_rlc`, `attribute_s`,
`lanes_invalid_per_wave`."""

from __future__ import annotations

import asyncio
import functools
import json
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import manifest as M, reference as R, reference_threshold as RT  # noqa: E402
from benchmark import reference_verify as RV, traffic as T  # noqa: E402
from benchmark.tests.attribution import Recorded  # noqa: E402
from charon_tpu.app import tracer  # noqa: E402
from charon_tpu.core import cryptoplane as cp, eth2data as d  # noqa: E402
from charon_tpu.core.evidence import EvidenceRegistry  # noqa: E402
from charon_tpu.core.parsigdb import ParSigDB  # noqa: E402
from charon_tpu.core.parsigex import Eth2Verifier, MemTransport, ParSigEx  # noqa: E402
from charon_tpu.core.sigagg import SigAgg  # noqa: E402
from charon_tpu.core.types import Duty, DutyType, pubkey_from_bytes  # noqa: E402
from charon_tpu.core.validatorapi import ValidatorAPI  # noqa: E402
from charon_tpu.crypto import g1g2, shamir  # noqa: E402
from charon_tpu.tbls.native_impl import NativeImpl  # noqa: E402
from tests.test_cryptoplane import FORK  # noqa: E402
from tests.test_tracer import _flush_stats  # noqa: E402

CELL = "dv-3of4-1k-byz.attest-forged"
NEW = ("sets_invalid_per_wave",)  # the cell's own; PR 35's five went in PR 37
# lists the cell stays out of: the node-down cell's two, and the two-kind cell's eight
SYNC_CELL = "dv-3of4-1k-sync.attest-sync"
SYNC_NEW = ("duty_p50_s.attester", "duty_p50_s.sync_message", "program_s.verify.sync_message",
            "program_s.recombine.sync_message", "kinds_per_flush",
            "vapi_submits_per_wave.sync_message", "lane_order_flips", "lane_yield_s")
LEFT_OUT = ("window_wait_s.verify", "sets_short_per_wave") + SYNC_NEW
FOUR = ("dv-4of7-1k.attest-slot", "dv-3of4-1k.attest-slot", "dv-5of7-1k.node-down", CELL)
N, THRESHOLD, VALIDATORS, SLOT, FORGER = 4, 3, 4, 37, 2
FORGED_ROOT = b"forged" + bytes(26)  # benchmark/serve.py's wrong_key partial


# -- a plane with the two verify tiers, and a seeded 3-of-4 wave ----------------


class TieredPlane:
    """tests/test_cryptoplane.FakePlane with `parallel/mesh`'s two verify
    tiers and its program hook: all lanes at once, and on a failure every
    lane alone. A lane's verdict is the C++ engine's (the program's second
    engine: fast, and not the reference this file compares with)."""

    def __init__(self, roots):
        self.t, self.on_program, self.native = THRESHOLD, None, NativeImpl()
        self.roots = {g1g2.g2_to_bytes(cp._msg_point(r)): r for r in roots}
        self.programs: list[str] = []

    def _tier(self, family, n, answer):
        t0 = time.monotonic()
        self.programs.append(family)
        if self.on_program is not None:
            self.on_program(f"mesh/{family}", time.monotonic() - t0, n)
        return answer

    def verify_host(self, pks, msgs, sigs, rng=None):
        lanes = [(g1g2.g1_to_bytes(pk), self.roots[g2_bytes(m)], g2_bytes(s))
                 for pk, m, s in zip(pks, msgs, sigs)]
        sound = self.native.verify_batch(lanes)
        if self._tier("verify_rlc", len(lanes), all(sound)):
            return [True] * len(lanes)
        return self._tier("verify", len(lanes), list(sound))

    def recombine_host(self, pubshares, msgs, partials, group_pks, indices, rng=None):
        sigs = [shamir.threshold_aggregate_g2(dict(zip(idx, row)))
                for idx, row in zip(indices, partials)]
        return self._tier("step_rlc", len(msgs), (sigs, [True] * len(msgs)))


g2_bytes = g1g2.g2_to_bytes


@pytest.fixture(scope="module")
def wave():
    """Four validators of a seeded 3-of-4 cluster attest in one slot, each in
    a committee of its own: keys, shares and partial signatures are the plain
    reference's, the signing roots the program's SSZ (held to the
    reference's)."""
    out = types.SimpleNamespace(pubkeys=[], secrets={}, atts={}, roots={}, partials={},
                                pubshares_by_idx={i: {} for i in range(1, N + 1)})
    for v in range(VALIDATORS):
        secret = R.seeded_scalar("forged-wave", v).to_bytes(32, "big")
        pk = pubkey_from_bytes(R.secret_to_public_key(secret))
        data = d.AttestationData(
            slot=SLOT, index=v, beacon_block_root=bytes([v + 1]) * 32,
            source=d.Checkpoint(epoch=0, root=bytes(32)),
            target=d.Checkpoint(epoch=1, root=b"\x33" * 32))
        att = d.Attestation(aggregation_bits=(True,), data=data)
        root = d.SignedData("attestation", att).signing_root(FORK, SLOT // 32)
        fields = (SLOT, v, data.beacon_block_root, 0, bytes(32), 1, b"\x33" * 32)
        assert root == R.attestation_signing_root(
            fields, FORK.fork_version, FORK.genesis_validators_root)
        out.pubkeys.append(pk)
        out.secrets[pk], out.atts[pk], out.roots[pk] = secret, att, root
        for idx, share in R.threshold_split(secret, N, THRESHOLD, "forged-wave", v).items():
            out.pubshares_by_idx[idx][pk] = R.secret_to_public_key(share)
            out.partials[idx, pk] = R.sign(share, root)
    out.forgeries = [
        R.sign(R.seeded_scalar("forger", 35, n).to_bytes(32, "big"), FORGED_ROOT)
        for n in range(2)]
    return out


@functools.cache
def reference_verdict(lane: tuple) -> bool:
    """The plain reference's answer for a (pubkey, root, signature) lane;
    the cases of a test share most lanes."""
    return RV.verify(*lane)


def serve_wave(wave, forged_at):
    """The wave through one node's own submitters: the VC's set through the
    ValidatorAPI, operators 2-4's through ParSigEx, over ONE coalescer;
    operator 2's partials at the positions `forged_at` of its set replaced by
    well-formed forgeries."""
    plane = TieredPlane(wave.roots.values())
    node_tracer, stats = tracer.Tracer(), []
    coalescer = cp.SlotCoalescer(
        plane, window=2.0, window_max=4.0, decode_workers=0,
        stats_hook=tracer.plane_span_bridge(node_tracer, inner_hook=stats.append))
    handle = Recorded(coalescer)
    evidence = EvidenceRegistry()
    verifier = Eth2Verifier(FORK, wave.pubshares_by_idx, plane=handle)
    parsigex = ParSigEx(1, MemTransport(), verifier=verifier, evidence=evidence,
                        tracer=node_tracer)
    parsigdb = ParSigDB(THRESHOLD)
    sigagg = SigAgg(threshold=THRESHOLD, fork=FORK, plane=handle,
                    pubshares_by_idx=wave.pubshares_by_idx)
    vapi = ValidatorAPI(1, wave.pubshares_by_idx[1], FORK, plane=handle,
                        roster=verifier.roster, tracer=node_tracer)
    by_data_root = {a.data.hash_tree_root(): pk for pk, a in wave.atts.items()}
    vapi.register_pubkey_by_attestation(lambda _slot, root: by_data_root[root])
    vapi.subscribe(parsigdb.store_internal)
    parsigex.subscribe(parsigdb.store_external)
    parsigdb.subscribe_threshold(sigagg.aggregate)
    aggregates: dict = {}

    async def on_aggregate(_duty, data_set):
        aggregates.update(data_set)

    sigagg.subscribe(on_aggregate)
    duty = Duty(SLOT, DutyType.ATTESTER)

    def peer_set(idx):
        signed = {}
        for pos, pk in enumerate(wave.pubkeys):
            sig = wave.partials[idx, pk]
            if idx == FORGER and pos in forged_at:
                sig = wave.forgeries[forged_at.index(pos)]
            signed[pk] = d.ParSignedData(
                d.SignedData("attestation", wave.atts[pk], sig), idx)
        return signed

    async def main():
        mine = [d.Attestation(a.aggregation_bits, a.data, wave.partials[1, pk])
                for pk, a in wave.atts.items()]
        await asyncio.wait_for(asyncio.gather(
            vapi.submit_attestations(mine),
            *(parsigex.receive(duty, peer_set(i), sender=i) for i in (2, 3, 4))), 60)

    try:
        asyncio.run(main())
    finally:
        coalescer.close()
    return types.SimpleNamespace(
        plane=plane, coalescer=coalescer, handle=handle, stats=stats, evidence=evidence,
        parsigex=parsigex, aggregates=aggregates, spans=node_tracer.dump())


# -- (b), (c): the served path, the forged lane anywhere in its set -----------------


@pytest.mark.parametrize("forged_at", [(), (0,), (2,), (3,), (1, 3)],
                         ids=["none", "first", "middle", "last", "two"])
def test_a_forged_lane_fails_its_own_set_and_no_other_wherever_it_sits(wave, forged_at):
    run = serve_wave(wave, forged_at)
    verify, recombine = run.stats
    # every lane's answer is the plain reference's
    assert len(run.handle.sets) == N
    for s in run.handle.sets:
        assert s["answers"] == [reference_verdict(lane) for lane in s["lanes"]]
    bad_sets = [s["answers"] for s in run.handle.sets if not all(s["answers"])]
    if forged_at:
        (answers,) = bad_sets  # operator 2's, and only the forged positions of it
        assert [pos for pos, ok in enumerate(answers) if not ok] == list(forged_at)
        assert run.plane.programs == ["verify_rlc", "verify", "step_rlc"]
    else:
        assert bad_sets == [] and run.plane.programs == ["verify_rlc", "step_rlc"]
    # the set is dropped WHOLE and billed to its sender, once; nobody else is
    assert run.parsigex.dropped_invalid == (1 if forged_at else 0)
    assert {i: run.evidence.count(i, "parsig_invalid") for i in range(1, N + 1)} == {
        1: 0, 2: 1 if forged_at else 0, 3: 0, 4: 0}
    # the flush says so
    assert (verify.verify_jobs, verify.lanes, verify.sets_expected, verify.sets_seen,
            verify.window_closed_by) == (N, N * VALIDATORS, N, N, "complete")
    assert (verify.attributed, verify.lanes_invalid, verify.sets_invalid) == (
        bool(forged_at), len(forged_at), 1 if forged_at else 0)
    assert verify.attribute_lanes == (N * VALIDATORS if forged_at else 0)
    assert (recombine.attributed, recombine.lanes_invalid, recombine.attribute_span) == (
        False, 0, None)
    assert run.coalescer.flushes_attributed == (1 if forged_at else 0)
    assert run.coalescer.lanes_invalid == len(forged_at)
    # ... and so does the node's own timeline
    attribute = [s for s in run.spans if s["name"] == "cryptoplane.attribute"]
    flushes = [s for s in run.spans if s["name"] == "cryptoplane.flush" and s["attrs"]["jobs"] == N]
    assert all(s["attrs"]["attributed"] is bool(forged_at) for s in flushes) and flushes
    if forged_at:
        assert verify.attribute_span is not None
        assert verify.device_span[0] <= verify.attribute_span[0] <= verify.attribute_span[1] \
            <= verify.device_span[1]
        device = {s["span_id"] for s in run.spans if s["name"] == "cryptoplane.device"}
        assert len(attribute) == N  # one under each submitting span's copy of the flush
        assert all(s["parent_id"] in device for s in attribute)
        assert [s["attrs"].get("shared", False) for s in attribute].count(False) == 1
        assert {(s["attrs"]["lanes"], s["attrs"]["lanes_invalid"], s["attrs"]["sets_invalid"])
                for s in attribute} == {(N * VALIDATORS, len(forged_at), 1)}
        oks = sorted(s["attrs"]["ok"] for s in run.spans if s["name"] == "parsigex.verify")
        assert oks == [False, True, True]
    else:
        assert attribute == [] and verify.attribute_span is None
    # every duty completes: exactly t partials, without the forger's where it forged
    assert len(run.handle.rows) == VALIDATORS == len(run.aggregates)
    for row in run.handle.rows:
        assert len(row["partials"]) == THRESHOLD
        assert row["indices"] == ([1, 3, 4] if forged_at else [1, 2, 3])
        assert row["aggregate"] == RT.recombine(dict(zip(row["indices"], row["partials"])))
    for pk in wave.pubkeys:
        assert run.aggregates[pk].signature == R.sign(wave.secrets[pk], wave.roots[pk])


# -- the coalescer's bookkeeping ----------------------------------------------------


def _lane(n=0):
    secret = R.seeded_scalar("unit-lane", n).to_bytes(32, "big")
    root = bytes([n + 1]) * 32
    return (R.secret_to_public_key(secret), root, R.sign(secret, root))


def test_a_lane_that_does_not_decode_is_invalid_and_attributes_nothing():
    """The flipped-byte partial of the `attest-slot` mix: the host's parse (or
    the RLC program's own mask) answers it; no per-lane program runs."""
    good, other = _lane(0), _lane(1)
    plane, stats = TieredPlane([good[1], other[1]]), []
    coalescer = cp.SlotCoalescer(plane, window=0.01, decode_workers=0, stats_hook=stats.append)
    garbled = (other[0], other[1], b"\x00" * 96)

    async def main():
        return await asyncio.gather(coalescer.verify([good]), coalescer.verify([other, garbled]))

    try:
        assert asyncio.run(main()) == [[True], [True, False]]
    finally:
        coalescer.close()
    (s,) = stats
    assert (s.attributed, s.lanes_invalid, s.sets_invalid, s.attribute_span) == (False, 1, 1, None)
    assert plane.programs == ["verify_rlc"] and coalescer.flushes_attributed == 0


def test_the_coalescer_listens_in_front_of_whoever_holds_the_program_hook():
    """app/run.py's plane factory sets the profiler's hook before the
    coalescer has the plane, the harness chains its own afterwards: both
    still hear every program, and a rebuilt plane is listened to as well."""
    lane = _lane(0)
    forged = (lane[0], lane[1], _lane(1)[2])
    before, after = [], []

    def factory():
        plane = TieredPlane([lane[1]])
        plane.on_program = lambda *sample: before.append(sample[0])
        return plane

    stats = []
    coalescer = cp.SlotCoalescer(factory(), window=0.01, decode_workers=0,
                                 stats_hook=stats.append, plane_factory=factory)
    inner = coalescer.plane.on_program
    coalescer.plane.on_program = lambda *sample: (after.append(sample[0]), inner(*sample))
    try:
        assert asyncio.run(coalescer.verify([lane, forged])) == [True, False]
    finally:
        coalescer.close()
    assert before == after == ["mesh/verify_rlc", "mesh/verify"]
    assert stats[0].attributed and stats[0].attribute_lanes == 2
    rebuilt = coalescer._listen(factory())
    rebuilt.on_program("mesh/verify_dec", 0.5, 7)
    assert coalescer._attributions[-1][2] == 7 and before[-1] == "mesh/verify_dec"
    # a plane without the hook is left as it is
    bare = types.SimpleNamespace(t=3)
    assert coalescer._listen(bare) is bare and not hasattr(bare, "on_program")


def test_the_bridge_hangs_the_attribution_under_the_device_stage():
    t = tracer.Tracer()
    tracer.plane_span_bridge(t)(_flush_stats(
        attributed=True, lanes_invalid=1, sets_invalid=1, attribute_span=(11.0, 11.3),
        attribute_lanes=12, parents=(("a" * 32, "b" * 16),)))
    tracer.plane_span_bridge(t)(_flush_stats(parents=(("c" * 32, "d" * 16),)))
    spans = {(s["trace_id"][0], s["name"]): s for s in t.dump()}
    attribute = spans["a", "cryptoplane.attribute"]
    assert attribute["parent_id"] == spans["a", "cryptoplane.device"]["span_id"]
    assert (attribute["start_us"], attribute["duration_us"]) == (11_000_000, 300_000)
    assert attribute["attrs"] == {"lanes": 12, "lanes_invalid": 1, "sets_invalid": 1}
    assert spans["a", "cryptoplane.flush"]["attrs"]["attributed"] is True
    assert ("c", "cryptoplane.attribute") not in spans
    assert spans["c", "cryptoplane.flush"]["attrs"]["attributed"] is False


def test_the_node_counts_attributed_flushes_and_invalid_lanes():
    from charon_tpu.app.metrics import ClusterMetrics

    text = (REPO / "charon_tpu/app/run.py").read_text()
    assert "metrics.plane_flushes_attributed" in text and "metrics.plane_lanes_invalid" in text
    m = ClusterMetrics("hash", "name", "peer")
    m.labels(m.plane_flushes_attributed).inc()
    m.labels(m.plane_lanes_invalid).inc(2)
    out = m.render().decode() if isinstance(m.render(), bytes) else m.render()
    assert "tpu_plane_flushes_attributed_total" in out and "tpu_plane_lanes_invalid_total" in out
    docs = (REPO / "docs/metrics.md").read_text()
    for name in ("tpu_plane_flushes_attributed_total", "tpu_plane_lanes_invalid_total",
                 "cryptoplane.attribute"):
        assert name in docs, name


# -- (d): the configuration, the mix, the cell, the metrics -----------------------


def _config(name="dv-3of4-1k-byz"):
    return json.loads((REPO / "benchmark/configs" / f"{name}.json").read_text())


def _mix():
    return json.loads((REPO / "benchmark/mixes/attest-forged.json").read_text())


def test_the_cell_is_in_the_manifest_with_its_per_layer_metrics():
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    cells = [w["name"] for w in man["workloads"]]
    assert tuple(cells[:4]) == FOUR  # later cells come after it
    cell = M.load_cell(REPO, CELL, man)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "dv-3of4-1k-byz", "attest-forged")
    assert [m.name for m in cell.end_to_end] == ["duty_p50_s", "duty_p95_s", "setup_s"]
    names = [m.name for m in cell.per_layer]
    assert len(names) == 18 and set(NEW) <= set(names) and not set(names) & set(LEFT_OUT)
    assert "program_s.verify" in names and "device_busy_s.verify" in names  # like the other three
    for m in cell.end_to_end + cell.per_layer:
        assert callable(M.load_reader(REPO, man, m.reader))
        assert m.moves in (None, "duty_p50_s")
    # its one metric is its alone; it is in every list the three older
    # cells share, fourth; a list holds every cell that reports it, and
    # whatever cell came later comes after the four
    for entry in man["per_layer"]:
        assert set(entry["workloads"]) <= set(cells)
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL]
        elif entry["name"] in LEFT_OUT or entry["workloads"][0] != FOUR[0]:
            assert CELL not in entry["workloads"]  # another cell's own metrics (PR 44's six too)
        else:
            assert tuple(entry["workloads"][:4]) == FOUR
    (entry,) = [c for c in man["configs"] if c["name"] == "dv-3of4-1k-byz"]
    cfg = _config()
    assert cfg["source"] == entry["source"] and sorted(cfg["reduced"]) == entry["reduced"]
    assert len(entry["source"]) <= 200 and "parsigex.go" in entry["source"]
    (workload,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert len(workload["why"]) <= 200 and "no verify_dec@128 since PR 36" in workload["why"]


def test_the_configuration_is_dv_3of4_1k_programs_and_all():
    """Since PR 37 the list is `dv-3of4-1k`'s as it stands: the per-lane
    program left it (never dispatched since PR 36)."""
    cfg, base = _config(), _config("dv-3of4-1k")
    differ = sorted(k for k in set(cfg) | set(base) if cfg.get(k) != base.get(k))
    assert differ == ["assumed", "deployment", "guarantees", "guarantees_exercised", "name",
                      "source"]
    assert cfg["programs"] == base["programs"] == [
        "verify_rlc_dec@128", "step_rlc_dec@32", "g1dec@512"]
    for key, value in base["guarantees"].items():
        assert cfg["guarantees"][key] == value  # none weaker
    assert cfg["guarantees"]["every_duty_completes_without_the_forgers_set"] is True
    assert cfg["guarantees"]["honest_sets_of_a_flush_with_a_refused_set_pass"] is True
    for key, value in base["assumed"].items():
        assert key == "validators" or cfg["assumed"][key] == value
    assert cfg["assumed"]["forger_share_index"].startswith("2:")
    assert "verify_dec@128 left the list in PR 37" in cfg["assumed"]["programs"]


@pytest.mark.parametrize("seed", [1, 3500000009, 2**31 + 12345])
def test_the_mix_lands_on_the_programs_the_configuration_lists(seed):
    cfg, mix = _config(), _mix()
    assert mix["fault"] == {"kind": "wrong_key", "operator": 2, "slots": "all", "partials": 1}
    assert (mix["duties"], mix["slots"], mix["send_jitter_ms"], mix["silent_operators"]) == (
        ["attester"], "window", 30, [])
    plan = T.make_plan(cfg, mix, seed)
    T.check_programs(plan, cfg)
    assert plan.senders() == 4 and plan.silent == ()
    assert sorted({plan.duties_in(p) * plan.senders() for p in range(32)}) == [124, 128]
    for slot in range(3):  # every slot of a window, operator 2 alone
        assert [i for i in range(1, 5) if plan.forged(slot, i, 2)] == [2]


def test_a_mix_on_a_configuration_that_lacks_its_programs_is_refused_before_boot(
        tmp_path, capsys):
    """Until PR 37 this very mix on `dv-3of4-1k` was the case (it asked for
    `verify_dec@128`); now the two configurations list the same programs
    and the mix passes there. The case that is refused: two kinds of duty
    on a configuration that compiles one kind's whole waves."""
    base = _config("dv-3of4-1k")
    T.check_programs(T.make_plan(base, _mix(), 7), base)
    sync_mix = json.loads((REPO / "benchmark/mixes/attest-sync.json").read_text())
    short = dict(base, name="dv-3of4-1k-short", sync_committee_members=64)  # more than it compiles
    with pytest.raises(T.TrafficError, match="verify_rlc_dec@256"):
        T.check_programs(T.make_plan(short, sync_mix, 7), short)
    # ... and so says run.py, in under a second, without importing jax
    from benchmark import run
    from benchmark.tests import helpers

    root = helpers.make_root(tmp_path)
    (root / "benchmark/configs/dv-3of4-1k-short.json").write_text(json.dumps(short))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "dv-3of4-1k-short", "source": "tests",
                                "file": "benchmark/configs/dv-3of4-1k-short.json",
                                "reduced": [], "why": "tests"})
    manifest["workloads"].append({"name": "dv-3of4-1k-short.attest-sync",
                                  "config": "dv-3of4-1k-short", "traffic": "attest-sync",
                                  "chips": 1, "why": "tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    codes = []
    t0 = time.monotonic()
    rc = run.main(["--workload", "dv-3of4-1k-short.attest-sync", "--seed", "7",
                   "--seconds", "36"], root=root, exit_fn=codes.append)
    assert (rc, codes) == (2, [3]) and time.monotonic() - t0 < 1.0  # the watchdog's exit code
    assert "before boot: TrafficError" in capsys.readouterr().err


@pytest.mark.parametrize("cell,name", [(CELL, NEW[0])] + [(SYNC_CELL, n) for n in SYNC_NEW[2:]])
def test_a_new_metric_reads_nothing_from_a_program_without_its_source(cell, name, monkeypatch):
    """A program from before the metric's field, span or program (the
    parent commit of the PR that brought it): the reader returns None and
    the line leaves the metric out. The forged cell's one metric (PR 37)
    and the two-kind cell's six that read what PR 39 added to the program
    (its two `duty_p50_s.<kind>` read the harness's own records)."""
    from benchmark import nodespans
    from benchmark.serve import RunData

    man = M.load_manifest(REPO)
    (metric,) = [m for m in M.load_cell(REPO, cell, man).per_layer if m.name == name]
    read = M.load_reader(REPO, man, metric.reader)
    run = RunData(window=(1000.0, 1036.0))
    old_flush = types.SimpleNamespace(verify_jobs=4, lanes=128)  # FlushStats before PR 35
    run.flushes = [(1005.0, old_flush)]
    run.programs = [("step_rlc_dec", 1.0, 32, 1007.0)]
    monkeypatch.setattr(nodespans, "node_spans", lambda: [])
    assert read(run, **metric.params) is None
    monkeypatch.setattr(nodespans, "node_spans", lambda: None)
    assert read(run, **metric.params) is None


def test_lanes_invalid_per_wave_sums_a_waves_verify_flushes():
    from benchmark.serve import RunData

    read = M.load_reader(REPO, M.load_manifest(REPO), "flush_attribution")
    run = RunData(window=(1000.0, 1036.0))

    def flush(at, verify_jobs, lanes_invalid):
        return (at, types.SimpleNamespace(verify_jobs=verify_jobs, lanes_invalid=lanes_invalid))

    run.flushes = [flush(1005.0, 4, 1), flush(1007.0, 0, 0),  # wave 1: verify, recombine
                   flush(1017.0, 2, 1), flush(1017.5, 2, 2), flush(1019.0, 0, 0),  # a split wave
                   flush(1029.0, 4, 0), flush(1031.0, 0, 0),  # an honest wave
                   flush(990.0, 4, 9)]  # before the window
    assert read(run) == 1.0  # median of 1, 3, 0


# -- the rehearsal: the mix's whole control flow on the CPU ---------------------------


@pytest.fixture(scope="module")
def rehearsal():
    """The rehearsal runs on the wall clock: on a loaded CPU a set can trail
    its wave past the window's timer and flush alone (tests/test_node_down.py).
    That is not what these tests are about, so such a run is made again,
    twice at most."""
    for _attempt in range(3):
        proc = subprocess.run(
            [sys.executable, str(REPO / "benchmark/tests/rehearse_forged.py")],
            capture_output=True, text=True, timeout=240, cwd=str(REPO))
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        seen = json.loads(lines[-1])
        if [f["verify_jobs"] for f in seen["flushes"] if f["verify_jobs"]] == [N] * 3:
            break
    return types.SimpleNamespace(line=json.loads(lines[-2]), seen=seen, stderr=proc.stderr)


def test_the_rehearsal_ends_correct_with_three_forged_sets_rejected(rehearsal):
    line = rehearsal.line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 10
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert "forged_sets_not_rejected 0 limit 0 ok" in rehearsal.stderr
    assert rehearsal.seen["parsig_invalid"] == {"1": 0, "2": 3, "3": 0, "4": 0}
    metrics = line["metrics"]
    assert metrics["sets_invalid_per_wave"] == {"value": 1.0, "unit": "count"}
    assert metrics["flushes_per_wave"]["value"] == 2.0
    assert not set(LEFT_OUT) & set(metrics)


def test_every_wave_of_the_rehearsal_is_attributed_and_recombines_without_the_forger(rehearsal):
    flushes = rehearsal.seen["flushes"]
    verify = [f for f in flushes if f["verify_jobs"]]
    recombine = [f for f in flushes if f["recombine_jobs"]]
    assert len(verify) == len(recombine) == 3
    for f in verify:
        assert (f["jobs"], f["sets_expected"], f["sets_seen"], f["sets_awaited"]) == (4, 4, 4, 4)
        assert (f["attributed"], f["lanes_invalid"], f["sets_invalid"]) == (True, 1, 1)
        assert f["attribute_lanes"] == f["lanes"] and f["attribute_span"] is not None
    for f in recombine:
        assert (f["attributed"], f["lanes_invalid"], f["attribute_span"]) == (False, 0, None)
    spans = rehearsal.seen["spans"]
    attribute = [s for s in spans if s["name"] == "cryptoplane.attribute"
                 and not s["attrs"].get("shared")]
    assert [(s["attrs"]["lanes_invalid"], s["attrs"]["sets_invalid"]) for s in attribute] == [
        (1, 1)] * 3
    oks = [s["attrs"]["ok"] for s in spans if s["name"] == "parsigex.verify"]
    assert sorted(oks) == [False] * 3 + [True] * 6  # the forger's set a slot, and no other
    aggregated = [s for s in spans if s["name"] == "sigagg.aggregate"]
    assert len(aggregated) == 3 and all(s["attrs"]["partials"] == 3 for s in aggregated)
    rows = rehearsal.seen["rows"]
    assert len(rows) == 10 and all(r["indices"] == [1, 3, 4] for r in rows)


def test_the_rehearsals_answers_are_the_plain_references_lane_for_lane(rehearsal):
    """Through the real node (ValidatorAPI over HTTP, ParSigEx over TCP, the
    tenant service, the coalescer): every lane of the first wave's four
    sets, and the forger's set of the others."""
    sets = rehearsal.seen["sets"]
    assert len(sets) == 12
    first_wave, forged = sets[:4], [s for s in sets if not all(s["answers"])]
    assert len(forged) == 3 and all(s["answers"].count(False) == 1 for s in forged)
    for s in first_wave + forged[1:]:
        lanes = [tuple(bytes.fromhex(x) for x in lane) for lane in s["lanes"]]
        assert s["answers"] == [reference_verdict(lane) for lane in lanes]
    for row in rehearsal.seen["rows"][:4]:
        partials = dict(zip(row["indices"], (bytes.fromhex(p) for p in row["partials"])))
        assert RT.recombine(partials).hex() == row["aggregate"]
        assert RV.verify(bytes.fromhex(row["group_pk"]), bytes.fromhex(row["root"]),
                         bytes.fromhex(row["aggregate"])) is True


def test_the_chip_comparison_runs_its_control_flow_on_the_cpu():
    """benchmark/tests/attribution.py is for the chip (ISSUE 35, tentpole 7);
    here its own control flow: the rehearsal's node over planepatch's plane,
    the first wave's lanes against the plain reference, the dispatch record."""
    driver = (
        "import sys, tempfile; from pathlib import Path\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        "from benchmark.tests import attribution, helpers, planepatch, rehearse_forged\n"
        "helpers.fake_trace()\n"
        "with tempfile.TemporaryDirectory() as tmp:\n"
        "    code = attribution.main(['--workload', rehearse_forged.CELL, '--seed', '3500000013',\n"
        "        '--seconds', '6', '--trace', '0'], root=rehearse_forged.make_root(Path(tmp)),\n"
        "        cpu=True, before=planepatch.host_plane)\n"
        "sys.stdout.flush(); import os; os._exit(code)\n")
    proc = subprocess.run([sys.executable, "-c", driver], capture_output=True, text=True,
                          timeout=240, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert json.loads(lines[-2])["correct"] is True
    seen = json.loads(lines[-1])["attribution"]
    assert seen["sets_of_the_wave"] == N and seen["lanes_that_differ"] == []
    assert seen["lanes_compared"] in (12, 16) and seen["lanes_served_invalid"] == 1
    assert [w["row_indices"] for w in seen["waves"]] == [[[1, 3, 4]]] * 2
    assert [sum(s["invalid"] for s in w["sets"]) for w in seen["waves"]] == [1, 1]
