"""The node's own duty timeline, end to end (ISSUE 26): one rehearsal of the
benchmark's served path on the CPU — the real `build_node` node beside its
in-process peers (bare QBFTConsensus + ParSigEx objects), the crypto-plane
service path patched in over a plane that runs no program
(benchmark/tests/planepatch.py), the recorded device trace standing in for
the profiler — and what the node's tracer, its hooks, /debug/duty's
timeline and the ten per-layer metrics say afterwards. The readers' own
tests (synthetic span forest) live in benchmark/tests/test_nodespans.py and
run here too."""

from __future__ import annotations

import json
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.tests.test_nodespans import *  # noqa: E402,F401,F403 — the readers' tests
from charon_tpu.app import tracer  # noqa: E402



def test_the_forged_cell_reports_the_verify_program_like_the_other_three():  # noqa: F811
    """benchmark/tests/test_duties.py's test of this name (which the
    `import *` above brings in) pins the manifest's size as PR 37 left it:
    20 per-layer metrics, 4 cells. PR 39 adds a cell and eight metrics and may
    not edit that file, so the later definition — this one, the one pytest
    collects — holds everything it holds but the two counts, which become
    the forged cell's own 18 and "every list's cells exist"."""
    import json as _json

    from benchmark import manifest as M

    cell = "dv-3of4-1k-byz.attest-forged"
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    cells = {w["name"] for w in man["workloads"]}
    names = [m.name for m in M.load_cell(REPO, cell, man).per_layer]
    assert len(names) == 18 and names[-1] == "sets_invalid_per_wave"
    assert {"program_s.verify", "device_busy_s.verify"} <= set(names)
    assert not {"window_wait_s.verify", "sets_short_per_wave"} & set(names)
    for entry in man["per_layer"]:  # every list explicit: a new cell joins the ones it reports
        assert entry["workloads"] and set(entry["workloads"]) <= cells
        assert (REPO / "benchmark/metrics" / f"{entry['name']}.json").exists()
    (workload,) = [w for w in man["workloads"] if w["name"] == cell]
    assert "one dispatch" in workload["why"] and len(workload["why"]) <= 200
    assert len(_json.dumps(man)) < 64 * 1024


# -- PR 44: what benchmark/tests/test_starts.py pinned of the PARENT program ------
#
# Three tests of that file (brought in by the `import *` above, frozen with
# the benchmark) assert what the parent lacked: `ValidatorAPI.
# submit_registrations` does not resolve, no configuration states `requires`,
# and the unpatched rehearsal at bare quorum loses every registration. PR 44
# brings the method, the sixth configuration that requires it, and the path
# that files a registration under the slot of its timestamp. The later
# definitions below — the ones pytest collects — hold everything the frozen
# ones hold but those three facts, which they hold the new way round.


def test_requires_names_what_is_missing_of_the_program():  # noqa: F811
    from benchmark import manifest as M

    name = "charon_tpu.core.validatorapi.ValidatorAPI.submit_registrations"
    assert M.unresolved([]) == []
    have = ["charon_tpu.core.validatorapi.ValidatorAPI.submit_registration", name,
            "charon_tpu.core.validatorapi.ValidatorAPI", "charon_tpu.core.deadline",
            "benchmark.traffic.Plan.wave_shapes"]
    lack = ["charon_tpu.core.validatorapi.ValidatorAPI.submit_registrations_split",
            "charon_tpu.core.no_such_module.Thing", "no_such_package.x", "charon_tpu.core.deadline.X.y"]
    assert M.unresolved(have + lack) == lack
    stated = {w["name"]: M.load_cell(REPO, w["name"]).config.get("requires")
              for w in M.load_manifest(REPO)["workloads"]}
    assert list(stated.values())[:5] == [None] * 5  # the five of PR 42 state none
    assert stated["dv-3of4-1k-reg.attest-register"] == [name]


def test_a_requirement_that_does_not_resolve_ends_the_run_before_boot():  # noqa: F811
    import time

    name = "charon_tpu.core.validatorapi.ValidatorAPI.submit_registrations_split"
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


def test_the_parent_program_files_its_vcs_registrations_under_slot_zero():  # noqa: F811
    """The frozen test of this name asserts the parent's gap: `--silent
    --unpatched` is `correct` false, `duties_missing` 6 of 6. The program
    now files each registration under the slot of its timestamp and a
    request is one set: the same run, NO patch, at bare quorum, completes
    all 13 duties."""
    from benchmark.tests import test_starts

    rc, (_info, line, seen), err = test_starts._rehearse("--silent", "--unpatched")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True and line["attempted"] == 13 and line["failed"] == 0
    assert all(c == {"value": 0, "limit": 0} for c in line["checks"].values())
    assert seen["patches"] == [] and len(seen["vc_rounds"]) == 1
    assert [s[0] for s in seen["peer_sends"]] == [3, 4]  # operator 2 silent: bare quorum


NEW = ("entry_self_s", "qbft_decide_s", "agg_bcast_self_s", "svc_queue_s", "window_wait_s",
       "idle_s.consensus", "idle_s.awaiting_input", "idle_s.entry", "idle_s.window",
       "idle_s.pack")


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/tests/rehearse_spans.py")],
        capture_output=True, text=True, timeout=240, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1]), proc.stderr


def test_the_node_records_only_its_own_spans_and_its_hooks_see_only_those(rehearsal):
    line, rings, _err = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert list(rings["nodes"]) == ["0"]  # the registry outlived the teardown
    ring = rings["nodes"]["0"]
    assert ring["evicted"] == 0 and len(ring["spans"]) < ring["capacity"] // 8
    names = Counter(s["name"] for s in ring["spans"])
    slots = sorted({s["attrs"]["slot"] for s in ring["spans"] if "slot" in s["attrs"]})
    # ONE instance a duty: the three peers' instances are in the global ring
    assert names["qbft.instance"] == len(slots)
    assert rings["global"]["qbft.instance"] == 3 * names["qbft.instance"]
    assert rings["global"]["qbft.deliver"] > names["qbft.deliver"] > 0
    # the peers are bare: no workflow edge, no plane
    assert set(rings["global"]) == {"qbft.deliver", "qbft.instance", "parsigex.receive"}
    # the node's span hook (core_step_latency_seconds) counted each of the
    # node's physical spans once, and nothing a peer recorded
    own = Counter(s["name"] for s in ring["spans"] if not s["attrs"].get("shared"))
    assert rings["hooked"] == dict(own)


def test_one_duty_reads_from_consensus_to_broadcast_nested_as_caused(rehearsal):
    _line, rings, _err = rehearsal
    spans = rings["nodes"]["0"]["spans"]
    slot = max(s["attrs"]["slot"] for s in spans if s["name"] == "broadcaster.broadcast")
    (timeline,) = [tl for tl in tracer.duty_timeline(slot, spans=spans)
                   if tl["duty"].endswith("/attester")]
    by_id = {s["span_id"]: s for s in timeline["spans"]}

    def parents(name):
        return {by_id[s["parent_id"]]["name"] if s["parent_id"] in by_id else ""
                for s in timeline["spans"] if s["name"] == name}

    assert parents("qbft.instance") <= {"consensus.propose", "qbft.deliver"}
    assert parents("vapi.submit") == {""}  # the VC's request roots its own branch
    assert parents("parsigex.verify") == {"parsigex.receive"}
    submitters = {"vapi.submit", "parsigex.verify", "sigagg.aggregate"}
    assert parents("cryptosvc.queue") == submitters
    assert parents("cryptoplane.window") == parents("cryptoplane.flush") == submitters
    for stage in ("cryptoplane.decode", "cryptoplane.pack", "cryptoplane.device"):
        assert parents(stage) == {"cryptoplane.flush"}
    assert parents("sigagg.aggregate") <= {"parsigdb.store_external", "parsigdb.store_internal"}
    assert parents("broadcaster.broadcast") == {"sigagg.aggregate"}
    # in time: decided, then the submissions, their queue, the window, the
    # stages, and the broadcast last
    first = {}
    for s in timeline["spans"]:
        first.setdefault(s["name"], s["offset_us"])
    order = ["qbft.instance", "vapi.submit", "cryptosvc.queue", "cryptoplane.window",
             "cryptoplane.pack", "cryptoplane.device", "sigagg.aggregate",
             "broadcaster.broadcast"]
    assert [first[n] for n in order] == sorted(first[n] for n in order)
    text = tracer.render_waterfall([timeline])
    assert all(name in text for name in order)


def test_a_traced_run_prints_the_ten_new_metrics_beside_the_old(rehearsal):
    line, _rings, err = rehearsal
    assert set(NEW) <= set(line["metrics"])
    # the old ones that a host-only node with the service path can report
    assert {"wave_host_s", "flush_window_s", "flush_pack_s", "flushes_per_wave"} <= set(
        line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(v["unit"] == "s" for k, v in line["metrics"].items() if k in NEW)
    assert 0 < m["qbft_decide_s"] < 3 and 0 < m["entry_self_s"] < m["wave_host_s"]
    assert 0 <= m["svc_queue_s"] < 2 and 0 < m["agg_bcast_self_s"] < m["wave_host_s"]
    # what the first job waited, beside the window configured
    assert 0.04 < m["window_wait_s"] < 2 and 0.04 < m["flush_window_s"] < 0.3
    # every idle instant of the traced window has one cause
    note = next(ln for ln in err.splitlines() if ln.startswith("node spans:"))
    causes = dict(p.strip().rsplit(" ", 1) for p in
                  note.split("by cause:")[1].split(";")[0].split(","))
    idle = line["device"]["window_s"] - line["device"]["busy_s"]
    assert sum(float(v) for v in causes.values()) == pytest.approx(idle, abs=1e-3)
    assert set(causes) == {"pack", "window", "entry", "consensus", "other", "pre_trigger",
                           "awaiting_input"}
    for name in NEW[5:]:
        assert m[name] == pytest.approx(float(causes[name.split(".", 1)[1]]), abs=1e-6)
    # the recorded trace is 0.786 s from the slot's start: before the trigger
    assert float(causes["pre_trigger"]) == pytest.approx(idle, abs=1e-3)
