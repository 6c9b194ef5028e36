"""Tests of the yardstick that need no chip.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import io
import json
import logging
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import cachedir, manifest as M, traffic as T, tracered  # noqa: E402
from benchmark.tests import helpers  # noqa: E402
from benchmark.watchdog import Watchdog  # noqa: E402

CELLS = ("dv-4of7-1k.attest-slot", "dv-3of4-1k.attest-slot")


def test_manifest_keeps_to_the_contract():
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert man["run_seconds"] % 12 == 0
    for cfg in man["configs"]:
        data = json.loads((REPO / cfg["file"]).read_text())
        assert sorted(data["reduced"]) == cfg["reduced"]
        assert data["source"] == cfg["source"]
    for name in CELLS:
        cell = M.load_cell(REPO, name, man)
        assert {m.name for m in cell.end_to_end} >= {"setup_s", "duty_p50_s"}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(M.load_reader(REPO, man, m.reader))


@pytest.mark.parametrize("fault", [
    ("workload name", lambda m: m["workloads"][0].update(name="has space")),
    ("unit", lambda m: m["end_to_end"][0].update(unit="tokens per second")),
    ("moves", lambda m: m["per_layer"][0].update(moves="nothing")),
    ("pair twice", lambda m: m["workloads"].append(dict(m["workloads"][0], name="again"))),
])
def test_validate_names_each_fault(fault):
    man = M.load_manifest(REPO)
    fault[1](man)
    assert M.validate(man), fault[0]


def test_a_cell_and_a_metric_are_added_as_files_only(tmp_path):
    root = helpers.make_root(tmp_path)
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "dv-4of7-1k.json").read_text())
    cfg.update(name="dv-4of7-2k", validators=1024,
               source="tests: a configuration added as a file")
    (bench / "configs" / "dv-4of7-2k.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "mixes" / "attest-slot.json").read_text())
    mix.update(name="attest-quiet", fault={"kind": "none"}, send_jitter_ms=0)
    (bench / "mixes" / "attest-quiet.json").write_text(json.dumps(mix))
    (bench / "metrics" / "flush_lanes.json").write_text(json.dumps({
        "name": "flush_lanes", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Coalescer", "moves": "duty_p50_s",
        "reader": "flush_lanes", "params": {"scale": 2}}))
    (bench / "readers" / "flush_lanes.py").write_text(
        "def read(run, scale):\n    return scale * sum(s.lanes for _t, s in run.flushes)\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "dv-4of7-2k", "source": cfg["source"],
                           "file": "benchmark/configs/dv-4of7-2k.json",
                           "reduced": sorted(cfg["reduced"]), "why": "tests"})
    man["workloads"].append({"name": "dv-4of7-2k.attest-quiet", "config": "dv-4of7-2k",
                             "traffic": "attest-quiet", "chips": 1, "why": "tests"})
    man["per_layer"].append({"name": "flush_lanes", "unit": "count", "better": "higher",
                             "source": "program_counter", "layer": "Coalescer",
                             "moves": "duty_p50_s",
                             "workloads": ["dv-4of7-2k.attest-quiet"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    assert M.validate(man) == []
    cell = M.load_cell(root, "dv-4of7-2k.attest-quiet")
    assert cell.config["validators"] == 1024 and cell.traffic["fault"]["kind"] == "none"
    assert [m.name for m in cell.per_layer] == ["flush_lanes"]
    plan = T.make_plan(cell.config, cell.traffic, 7)
    T.check_programs(plan, cell.config)  # 32 duties in every slot: still 256 / 32
    read = M.load_reader(root, man, cell.per_layer[0].reader)

    class Flush:
        lanes = 224

    class Run:
        flushes = [(0.0, Flush()), (1.0, Flush())]

    assert read(Run(), **cell.per_layer[0].params) == 896
    # the old cells do not report the new metric
    assert "flush_lanes" not in [m.name for m in M.load_cell(root, CELLS[0]).per_layer]


@pytest.mark.parametrize("seed", [1, 22, 2**31 + 12345])
def test_every_seed_gives_the_mainnet_shape_and_the_listed_programs(seed):
    for name in CELLS:
        cell = M.load_cell(REPO, name)
        plan = T.make_plan(cell.config, cell.traffic, seed)
        T.check_programs(plan, cell.config)
        counts = [plan.duties_in(p) for p in range(plan.slots_per_epoch)]
        assert sorted(set(counts)) == [31, 32] and sum(counts) == 1000
        assert sorted(v for p in range(32) for v in plan.members(p)) == list(range(1000))
    other = T.make_plan(cell.config, cell.traffic, seed + 1)
    assert other.members(0) != plan.members(0)
    assert T.make_plan(cell.config, cell.traffic, seed).members(0) == plan.members(0)


def test_the_bucket_precheck_rejects_a_33_duty_slot():
    cell = M.load_cell(REPO, CELLS[0])
    cfg = dict(cell.config, validators=1056)  # 33 duties in every slot
    plan = T.make_plan(cfg, cell.traffic, 5)
    with pytest.raises(T.TrafficError, match=r"step_rlc_dec@64.*duties a slot \{'attester': \[33\]\}"):
        T.check_programs(plan, cfg)


def test_the_bucket_precheck_rejects_a_3of4_wave_of_129_lanes():
    cell = M.load_cell(REPO, CELLS[1])
    cfg = dict(cell.config, validators=43 * 32)
    mix = dict(cell.traffic, silent_operators=[2], fault={"kind": "none"})
    plan = T.make_plan(cfg, mix, 5)  # three sets a wave, 43 duties: 129 lanes
    with pytest.raises(T.TrafficError, match=r"verify_rlc_dec@256.*lanes \{'attester': \[129\]\}"):
        T.check_programs(plan, cfg)


def test_traffic_that_cannot_complete_a_duty_is_refused():
    cell = M.load_cell(REPO, CELLS[1])
    with pytest.raises(T.TrafficError, match="fewer than t honest"):
        T.make_plan(cell.config, dict(cell.traffic, silent_operators=[2]), 5)


def _last_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_the_watchdog_ends_a_stalled_phase_with_one_failing_last_line():
    out, err, codes = io.StringIO(), io.StringIO(), []
    wd = Watchdog(60.0, out=out, err=err, exit_fn=codes.append)
    wd.dumpers.append(lambda: "open flushes: inflight=1")
    with wd.phase("programs", 0.3):
        deadline = time.monotonic() + 5
        while not codes and time.monotonic() < deadline:
            time.sleep(0.05)
    wd.close()
    assert codes == [3]
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[0])
    assert tuple(line)[:5] == M.LAST_LINE_KEYS
    assert line["correct"] is False and line["metrics"] == {}
    assert "programs" in line["error"] and line["phase"] == "programs"
    assert "open flushes" in err.getvalue() and "phase programs: start" in err.getvalue()


def test_a_deadlines_failing_line_says_which_second_ran_out():
    """Every finished phase's seconds, the open phase's so far and the
    run's, on the failing last line itself: the driver's record keeps
    that line when it keeps nothing else."""
    out, codes = io.StringIO(), []
    wd = Watchdog(60.0, out=out, err=io.StringIO(), exit_fn=codes.append)
    with wd.phase("cluster", 5):
        time.sleep(0.12)
    with wd.phase("programs", 5):
        time.sleep(0.06)
    with wd.phase("trace_stop", 0.3):
        deadline = time.monotonic() + 5
        while not codes and time.monotonic() < deadline:
            time.sleep(0.05)
    wd.close()
    assert codes == [3]
    line = _last_line(out.getvalue())
    assert tuple(line)[:5] == M.LAST_LINE_KEYS and line["phase"] == "trace_stop"
    took = line["phase_seconds"]
    assert list(took) == ["cluster", "programs", "open:trace_stop", "run"]
    assert 0.12 <= took["cluster"] < 0.3 and 0.06 <= took["programs"] < 0.2
    assert 0.3 <= took["open:trace_stop"] < 1.0
    assert took["run"] >= took["cluster"] + took["programs"] + took["open:trace_stop"]
    # an exception in the served path ends the run through the same door
    out2, codes2 = io.StringIO(), []
    wd2 = Watchdog(60.0, out=out2, err=io.StringIO(), exit_fn=codes2.append)
    with wd2.phase("node", 5):
        pass
    wd2.fail("RuntimeError: the node would not build")
    wd2.close()
    assert codes2 == [3] and list(_last_line(out2.getvalue())["phase_seconds"]) == ["node", "run"]


def test_the_watchdog_holds_the_whole_run_to_its_deadline():
    out, codes = io.StringIO(), []
    wd = Watchdog(0.3, out=out, err=io.StringIO(), exit_fn=codes.append)
    deadline = time.monotonic() + 5
    while not codes and time.monotonic() < deadline:
        time.sleep(0.05)
    wd.close()
    assert codes == [3] and "deadline" in _last_line(out.getvalue())["error"]


def test_an_offlist_bucket_ends_the_run_naming_family_bucket_and_lanes():
    from benchmark import serve

    class Plane:
        on_program = None

        @staticmethod
        def bucket_lanes(n):
            return T.bucket_lanes(n)

    class Coalescer:
        plane = Plane()
        warmup_hook = stats_hook = None

    out, codes = io.StringIO(), []
    wd = Watchdog(60.0, out=out, err=io.StringIO(), exit_fn=codes.append)
    cell = M.load_cell(REPO, CELLS[0])
    plan = T.make_plan(cell.config, cell.traffic, 1)
    server = serve.Server(cell, plan, 1, wd, None, None, set(cell.config["programs"]))
    server.coalescer = Coalescer()
    server.hook_plane()
    Coalescer.plane.on_program("mesh/verify_rlc_dec", 1.1, 224)  # the whole wave
    assert codes == []
    Coalescer.plane.on_program("mesh/verify_rlc_dec", 0.6, 100)  # a split wave
    wd.close()
    assert codes == [3]
    line = _last_line(out.getvalue())
    assert (line["family"], line["bucket"], line["lanes"]) == ("mesh/verify_rlc_dec", 128, 100)
    assert "verify_rlc_dec@128" in line["error"]


def test_a_warm_miss_and_a_miss_in_the_window_each_name_the_keys():
    marker = {"keys": ["jit_local-aaa", "jit_local-bbb"]}
    warm = cachedir.miss_reason("cell", marker, False, "jit_local", "jit_local-ccc")
    assert "jit_local-ccc" in warm and "jit_local-aaa" in warm and "dropped" in warm
    inside = cachedir.miss_reason("cell", None, True, "jit_local", "jit_local-ddd")
    assert "INSIDE the window" in inside and "jit_local-ddd" in inside
    assert cachedir.miss_reason("cell", None, False, "jit_local", "k") is None
    # jax's own log lines are what tells a miss of a plane program
    seen, log = [], cachedir.CacheLog()
    log.on_plane_miss = lambda module, key: seen.append((module, key))
    for text in ("PERSISTENT COMPILATION CACHE MISS for 'jit_iota' with key 'jit_iota-1'",
                 "PERSISTENT COMPILATION CACHE MISS for 'jit_local' with key 'jit_local-2'",
                 "Writing jit_local to persistent compilation cache with key 'jit_local-2'",
                 "Persistent compilation cache hit for 'jit_local' with key 'jit_local-3'"):
        log.emit(logging.LogRecord("jax", logging.DEBUG, "", 0, text, None, None))
    assert seen == [("jit_local", "jit_local-2")]
    assert cachedir.is_plane("jit_local_step") and not cachedir.is_plane("jit_iota")
    assert log.written == [("jit_local", "jit_local-2")]
    assert [k for _m, k, _t in log.hits] == ["jit_local-3"]


def test_the_marker_needs_its_entries_its_versions_and_its_sources(tmp_path):
    vers = {"jax": "0.9.0"}
    cachedir.write_marker(tmp_path, "cell", vers, "src-1", ["jit_local-a"])
    assert cachedir.read_marker(tmp_path, "cell", vers, "src-1") is None  # entry missing
    (tmp_path / "jit_local-a-cache").write_bytes(b"x")
    assert cachedir.read_marker(tmp_path, "cell", vers, "src-1")["keys"] == ["jit_local-a"]
    assert cachedir.read_marker(tmp_path, "cell", {"jax": "0.9.1"}, "src-1") is None
    assert cachedir.read_marker(tmp_path, "other", vers, "src-1") is None
    # a later PR edits the program in a checkout whose cache survived: the
    # marker is void, the run compiles instead of failing on a stale key
    assert cachedir.read_marker(tmp_path, "cell", vers, "src-2") is None
    cachedir.drop_marker(tmp_path, "cell")
    assert cachedir.read_marker(tmp_path, "cell", vers, "src-1") is None
    cachedir.drop_marker(tmp_path, "cell")  # twice is no fault


def test_the_sources_hash_moves_with_the_program_the_harness_and_the_configuration(tmp_path):
    for rel in ("charon_tpu/ops/limb.py", "benchmark/serve.py", "benchmark/tests/helpers.py"):
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text("x = 1\n")
    cfg = tmp_path / "cfg.json"
    cfg.write_text("{}")
    first = cachedir.sources_hash(tmp_path, cfg)
    assert cachedir.sources_hash(tmp_path, cfg) == first
    (tmp_path / "benchmark/tests/helpers.py").write_text("x = 2\n")  # on no tracing stack
    assert cachedir.sources_hash(tmp_path, cfg) == first
    seen = {first}
    for rel in ("charon_tpu/ops/limb.py", "benchmark/serve.py", "cfg.json"):
        (tmp_path / rel).write_text("x = 3\n")
        seen.add(cachedir.sources_hash(tmp_path, cfg))
    assert len(seen) == 4


def test_the_cache_is_fixed_inside_the_checkout(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else",
           "JAX_COMPILATION_CACHE_MAX_SIZE": "201326592"}
    cache, named = cachedir.configure(tmp_path, env)
    assert cache == tmp_path / "benchmark" / ".cache" / "jax" and cache.is_dir()
    assert named == "/somewhere/else"
    assert env == {"JAX_COMPILATION_CACHE_DIR": str(cache)}


def test_the_trace_reducer_on_the_recorded_trace():
    """tests/data/tiny.xplane.pb: recorded on a v5e by tests/record_trace.py —
    two jits, three calls each, 50-100 ms apart, in a 0.803 s window."""
    window = helpers.RECORDED_WINDOW_S
    s = tracered.reduce_file(str(helpers.RECORDED), 100.0, window)
    assert s.devices == 1 and s.events == 12
    names = [n.split("(")[0] for n, _t, _d in s.modules]
    assert names == ["jit_verify_like", "jit_recombine_like"] * 3
    assert all(b > a for a, b in s.busy)
    assert 1e-5 < s.busy_s < 3e-5  # 12 operations of 0-2 us
    assert abs(sum(d for _n, _t, d in s.modules) - s.busy_s) < 5e-6
    gaps = s.idle_gaps()
    assert gaps[0][1] - gaps[0][0] > 0.3  # after the last call
    assert abs(sum(b - a for a, b in gaps) + s.busy_s - window) < 1e-9
    bd = s.breakdown()
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(n.startswith("%") and " " not in n for n, _s in bd["device_ops"])

    class Run:
        trace = s
        # one dispatch span around the first call: its module is 3.7 us on the device
        programs = [("verify_rlc_dec", 0.002, 224, 100.0 + 0.0437 + 0.0005)]
        window = (99.0, 101.0)
        slot_duration = 12.0

        def in_window(self, ts):
            return True

    busy = M.load_reader(REPO, M.load_manifest(REPO), "device_busy")(Run(), family="verify")
    assert abs(busy - 3.7e-06) < 1e-9
    Run.trace = None  # a reader that finds nothing to read returns nothing
    assert M.load_reader(REPO, M.load_manifest(REPO), "device_busy")(Run(), family="verify") is None


def _rehearse(*extra) -> tuple[int, dict, str]:
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/tests/rehearse.py"), *extra],
        capture_output=True, text=True, timeout=180, cwd=str(REPO))
    return proc.returncode, _last_line(proc.stdout), proc.stderr


@pytest.fixture(scope="module")
def sound_run():
    return _rehearse()


def test_the_last_lines_keys_are_exactly_the_contracts(sound_run):
    rc, line, err = sound_run
    assert rc == 0
    assert tuple(line) == M.LAST_LINE_KEYS + ("checks",)
    assert line["correct"] is True and line["attempted"] == 7 and line["failed"] == 0
    assert set(line["metrics"]) == {"duty_p50_s", "duty_p95_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # each number compared beside its limit: in the line and as stderr's last lines
    assert all(set(c) >= {"value", "limit"} for c in line["checks"].values())
    tail = err.strip().splitlines()[-(len(line["checks"]) + 2):]
    assert tail[0].startswith("correctness:") and tail[-1] == "correct: True"
    assert "phase teardown: end" in err and "phase slot 2/2" in err


def _over_limit(line: dict) -> dict:
    return {k: c["value"] for k, c in line["checks"].items() if c["value"] > c["limit"]}


def test_a_traced_run_reports_the_devices_seconds_and_a_breakdown():
    """The profiler has no device plane on the CPU: the recorded trace
    stands in for it; everything else of a --trace 1 run is driven."""
    rc, line, err = _rehearse("--fake-trace", "--trace", "1")
    assert rc == 0 and line["correct"] is True
    assert tuple(line) == M.LAST_LINE_KEYS + ("breakdown", "checks")
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes",
                                   "busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    assert "wave_host_s" in line["metrics"] and "duty_p50_s" not in line["metrics"]
    # the host-only node flushes nothing: those readers return nothing
    assert "flushes_per_wave" not in line["metrics"]
    # the end of the trace is awaited last, after teardown and the reference
    assert err.index("phase reference: end") < err.index("phase trace_stop: start")


def test_the_control_comes_out_not_correct():
    """The control of `correct` (helpers.unchecked_recombine), at a size a
    test can hold: every duty's aggregate differs from the reference's."""
    rc, line, _err = _rehearse("--patch", "unchecked_recombine")
    assert rc == 0 and line["correct"] is False
    assert _over_limit(line) == {"aggregates_differ": 7}
    assert line["attempted"] == 7 and line["failed"] == 0  # on time, and wrong


def test_a_broken_timed_path_comes_out_not_correct():
    """The harness's look for a chip skipped, the rest of a run driven, the
    node's threshold aggregation altering its answer where it is produced:
    the node's own check refuses to broadcast it."""
    rc, line, _err = _rehearse("--patch", "altered_aggregate")
    assert rc == 0 and line["correct"] is False
    assert _over_limit(line) == {"duties_missing": 7}
    assert line["failed"] == line["attempted"] == 7


def test_a_node_that_trusts_its_peers_comes_out_not_correct():
    """The forged set is never rejected. What becomes of its one flipped
    partial is a race the node does not decide: where it is among the first
    t of its duty, the node's own check of the group signature refuses the
    whole last wave's aggregates (the duty is the slot's: every validator of
    it goes missing), and where it is not, nothing else shows."""
    rc, line, _err = _rehearse("--patch", "trusted_peers")
    assert rc == 0 and line["correct"] is False
    over = _over_limit(line)
    assert over.pop("forged_sets_not_rejected") == 1
    last_wave = 3  # of the rehearsal's 7 duties, 4 + 3 by slot
    assert over in ({}, {"duties_missing": last_wave}) and line["failed"] in (0, last_wave)


def test_without_a_chip_the_benchmark_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "36", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/tmp"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "TPU chip" in proc.stderr


def test_a_window_that_is_no_whole_number_of_slots_is_refused_before_boot():
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark/run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, timeout=60, cwd=str(REPO))
    assert proc.returncode != 0
    assert "whole number" in _last_line(proc.stdout)["error"]


def test_the_end_of_the_trace_is_started_where_the_program_returns():
    """serve.hook_plane's program hook tells `on_program_end` at once, on the
    dispatching thread and before the node's own hook: run.py starts the end
    of the profiler's session there, so that the wave's next program finds
    the device tracer stopped (README.md "--trace 1")."""
    import types

    from benchmark import run as runlib, serve as servelib

    order = []
    plane = types.SimpleNamespace(
        on_program=lambda family, seconds, lanes: order.append(("node", family)),
        bucket_lanes=lambda lanes: 16)
    fake = types.SimpleNamespace(
        coalescer=types.SimpleNamespace(plane=plane, warmup_hook=None, stats_hook=None),
        run=types.SimpleNamespace(programs=[], flushes=[]), warm_stats=[],
        allowed={"verify_rlc_dec@16"}, wd=None,
        on_program_end=lambda family, at: order.append(("harness", family, at)))
    servelib.Server.hook_plane(fake)
    before = time.time()
    plane.on_program("mesh/verify_rlc_dec", 0.7, 12)
    assert [o[:2] for o in order] == [("harness", "verify_rlc_dec"), ("node", "mesh/verify_rlc_dec")]
    assert before <= order[0][2] <= time.time()
    assert fake.run.programs == [("verify_rlc_dec", 0.7, 12, order[0][2])]
    fake.on_program_end = None  # an untraced run: nobody listens
    plane.on_program("mesh/verify_rlc_dec", 0.7, 12)
    assert len(order) == 3 and len(fake.run.programs) == 2
    # the first wave, to its verify program, held longer than the device tracer needs
    assert (runlib.TRACED_WAVE, runlib.TRACE_UNTIL) == (0, "verify")
    assert 0.02 <= runlib.TRACE_HOLD <= 0.05
