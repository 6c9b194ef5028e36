"""A cluster at bare quorum (ISSUE 28: `dv-5of7-1k.node-down`): what the
coalescer's flush, the `cryptoplane.window` and `sigagg.aggregate` spans and
the two new per-layer metrics say when operators are silent; that a wave
short of a set it AWAITS waits out its timer and grows the next window (a
run's first wave awaits all n; from the second on the node's roster awaits
the operators that sent last slot, ISSUE 33: tests/test_wave_roster.py);
that the new configuration and mix pass the harness's pre-boot checks and a
mix below quorum does not; and one rehearsal of the cell's control flow on
the CPU (benchmark/tests/rehearse_nodedown.py: the tests' 3-of-4 cluster,
one operator silent, wave hints passed through)."""

from __future__ import annotations

import asyncio
import json
import subprocess
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import manifest as M, traffic as T  # noqa: E402
from charon_tpu import tbls  # noqa: E402
from charon_tpu.app import tracer  # noqa: E402
from charon_tpu.tbls.python_impl import PythonImpl  # noqa: E402
from tests.test_cryptoplane import (  # noqa: E402,F401 — `clock` is a fixture
    YEAR, _all, _coalescer, _duty_workload, _lane, _ring_timer, _settle, clock,
)
from tests.test_tracer import _flush_stats  # noqa: E402

CELL = "dv-5of7-1k.node-down"
NEW = ("window_wait_s.verify", "sets_short_per_wave")


def _config(name="dv-5of7-1k"):
    return json.loads((REPO / "benchmark/configs" / f"{name}.json").read_text())


def _mix(name="node-down"):
    return json.loads((REPO / "benchmark/mixes" / f"{name}.json").read_text())


def _reader(name):
    return M.load_reader(REPO, M.load_manifest(REPO), name)


def _recombine_row():
    """One validator's recombine job, as keyword arguments of
    `SlotCoalescer.recombine` (tests/test_cryptoplane's workload)."""
    impl = PythonImpl()
    tbls.set_implementation(impl)
    _pk, gpk, psigs, root, _want, ps = _duty_workload(impl, slot=5)
    return dict(pubshares=[[ps[i] for i in (1, 2, 3)]], roots=[root],
                partials=[[p.data.signature for p in psigs]], group_pks=[gpk],
                indices=[[1, 2, 3]])


# -- the coalescer's ledger on the flush --------------------------------------


@pytest.mark.parametrize("seen", [5, 6, 7])
def test_a_flush_says_how_many_sets_its_wave_expected_and_how_many_came(clock, seen):
    coal, fake, stats = _coalescer()
    wave = (("duty-5", 7),)

    async def main():
        jobs = [asyncio.create_task(coal.verify([_lane()], wave=wave)) for _ in range(seen)]
        await _settle()
        if seen < 7:
            assert fake.verify_calls == 0 and not coal._flush_task.done()
            _ring_timer(coal, clock)
        return await _all(*jobs)

    try:
        assert asyncio.run(main()) == [[True]] * seen
    finally:
        coal.close()
    (s,) = stats
    assert (s.sets_expected, s.sets_seen) == (7, seen)
    assert (s.verify_jobs, s.recombine_jobs, s.jobs) == (seen, 0, seen)
    assert s.window_closed_by == ("complete" if seen == 7 else "timer")


def test_two_waves_in_one_window_are_summed_and_a_job_without_a_hint_voids_the_count(clock):
    coal, _fake, stats = _coalescer()

    async def window(*waves):
        jobs = [asyncio.create_task(coal.verify([_lane()], wave=w)) for w in waves]
        await _settle()
        _ring_timer(coal, clock)
        await _all(*jobs)

    async def main():
        # two duties' waves in one window: 2 of 4 and 3 of 4
        await window(*[(("A", 4),)] * 2, *[(("B", 4),)] * 3)
        # a request that spans two duties is one job of each wave
        await window((("A", 4), ("B", 4)), (("A", 4),))
        await window((("A", 4),), None)

    try:
        asyncio.run(main())
    finally:
        coal.close()
    assert [(s.sets_expected, s.sets_seen, s.verify_jobs) for s in stats] == [
        (8, 5, 5), (8, 3, 2), (None, None, 2)]


def test_a_recombine_window_carries_no_set_count_and_one_window_can_hold_both_queues(clock):
    row = _recombine_row()
    coal, _fake, stats = _coalescer()

    async def main():
        await asyncio.wait_for(coal.recombine(**row, wave=(("A", 1),)), 30)
        both = [asyncio.create_task(coal.verify([_lane()], wave=(("B", 2),))),
                asyncio.create_task(coal.recombine(**row, wave=(("B", 1),)))]
        await _settle()
        _ring_timer(coal, clock)
        await _all(*both)

    try:
        asyncio.run(main())
    finally:
        coal.close()
    alone, both = stats
    assert (alone.verify_jobs, alone.recombine_jobs) == (0, 1)
    assert alone.window_closed_by == "complete"
    assert (alone.sets_expected, alone.sets_seen) == (None, None)
    assert (both.verify_jobs, both.recombine_jobs, both.jobs) == (1, 1, 2)
    assert (both.sets_expected, both.sets_seen) == (2, 1)


def test_a_short_wave_grows_the_next_window_and_a_whole_recombine_leaves_it(clock):
    """The behaviour the cell is the baseline of (ROADMAP Speed 5a): five
    of seven sets are two jobs or more, so the timer close feeds the
    controller as load; the recombine window closes `complete` and feeds
    it nothing, so the growth stands until `window_max` caps it."""
    row = _recombine_row()
    coal, _fake, stats = _coalescer()

    async def main():
        for slot in range(3):
            jobs = [asyncio.create_task(coal.verify([_lane()], wave=((slot, 7),)))
                    for _ in range(5)]
            await _settle()
            _ring_timer(coal, clock)
            await _all(*jobs)
            await asyncio.wait_for(coal.recombine(**row, wave=((slot, 1),)), 30)

    try:
        asyncio.run(main())
    finally:
        coal.close()
    assert [s.window_closed_by for s in stats] == ["timer", "complete"] * 3
    assert [s.window / YEAR for s in stats] == pytest.approx([1, 1.5, 1.5, 2, 2, 2])
    assert coal.windows_closed == {"timer": 3, "complete": 3}


# -- the spans ------------------------------------------------------------------


def test_the_window_span_tells_a_degraded_cluster_from_unhinted_traffic():
    t = tracer.Tracer()
    hook = tracer.plane_span_bridge(t)
    tid = "c" * 32
    hook(_flush_stats(jobs=5, verify_jobs=5, sets_expected=7, sets_seen=5,
                      parents=((tid, "1" * 16),)))
    hook(_flush_stats(jobs=1, verify_jobs=0, recombine_jobs=1, window_closed_by="complete",
                      parents=((tid, "2" * 16),)))
    hook(_flush_stats(jobs=5, verify_jobs=5, parents=((tid, "3" * 16),)))
    short, recombine, unhinted = [s.attrs for s in t.spans if s.name == "cryptoplane.window"]
    assert (short["sets_expected"], short["sets_seen"], short["verify_jobs"]) == (7, 5, 5)
    assert recombine["recombine_jobs"] == 1 and recombine["verify_jobs"] == 0
    for attrs in (recombine, unhinted):
        assert "sets_expected" not in attrs and "sets_seen" not in attrs
    assert unhinted["closed_by"] == "timer" and unhinted["verify_jobs"] == 5


def test_annotate_reaches_only_the_span_it_names():
    t = tracer.Tracer()
    with tracer.span("sigagg.aggregate", tracer=t) as outer:
        tracer.annotate("sigagg.aggregate", partials=5)
        with tracer.span("aggsigdb.store", tracer=t) as inner:
            tracer.annotate("sigagg.aggregate", partials=9)
    tracer.annotate("sigagg.aggregate", partials=9)  # no span open: nothing
    assert outer.attrs["partials"] == 5 and "partials" not in inner.attrs


# -- the readers ------------------------------------------------------------------


def _run_of(flushes):
    return types.SimpleNamespace(
        flushes=[(10.0 + i, s) for i, s in enumerate(flushes)],
        in_window=lambda ts: True)


def test_sets_short_is_the_median_over_verify_flushes_and_none_without_the_fields():
    read = _reader("wave_sets_short")
    verify = [_flush_stats(verify_jobs=5, sets_expected=7, sets_seen=n) for n in (5, 5, 4)]
    recombine = _flush_stats(recombine_jobs=1)
    assert read(_run_of([verify[0], recombine, verify[1], recombine, verify[2]])) == 2.0
    assert read(_run_of([recombine])) is None
    # a program from before the fields (the parent of ISSUE 28)
    old = types.SimpleNamespace(jobs=5, lanes=160, window=0.3)
    assert read(_run_of([old, old])) is None


def test_window_wait_verify_reads_only_windows_that_held_a_verify_wave(monkeypatch):
    from benchmark import nodespans

    def span(start, seconds, **attrs):
        return types.SimpleNamespace(name="cryptoplane.window", start=start,
                                     end=start + seconds, attrs=attrs)

    read = _reader("span_duration_where")
    run = types.SimpleNamespace(in_window=lambda ts: True)
    spans = [span(1, 0.30, verify_jobs=5, recombine_jobs=0),
             span(2, 0.001, verify_jobs=0, recombine_jobs=1),
             span(3, 0.45, verify_jobs=5, recombine_jobs=0),
             span(3, 0.45, verify_jobs=5, recombine_jobs=0, shared=True),
             span(4, 0.002, verify_jobs=0, recombine_jobs=1),
             span(5, 0.60, verify_jobs=4, recombine_jobs=1)]
    monkeypatch.setattr(nodespans, "node_spans", lambda: spans)
    params = dict(span="cryptoplane.window", positive="verify_jobs")
    assert read(run, **params) == pytest.approx(0.45)
    # spans of a program from before the attribute, and no ring at all
    monkeypatch.setattr(nodespans, "node_spans", lambda: [span(1, 0.3, jobs=5)])
    assert read(run, **params) is None
    monkeypatch.setattr(nodespans, "node_spans", lambda: None)
    assert read(run, **params) is None


# -- the configuration, the mix, the cell -----------------------------------------


def test_the_cell_is_in_the_manifest_with_every_per_layer_metric():
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    cell = M.load_cell(REPO, CELL, man)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "dv-5of7-1k", "node-down")
    assert [m.name for m in cell.end_to_end] == ["duty_p50_s", "duty_p95_s", "setup_s"]
    names = [m.name for m in cell.per_layer]
    assert len(names) == 19 and tuple(names[-2:]) == NEW
    for m in cell.end_to_end + cell.per_layer:
        assert callable(M.load_reader(REPO, man, m.reader))
    # the two new metrics are this cell's alone; it is in every list the
    # first cell is in (the lists cells share), and in no later cell's own
    # metrics, whichever still later cell joined those (PR 44 joined three of
    # the two-kind cell's eight)
    for entry in man["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL]
        else:
            shared = entry["workloads"][0] == "dv-4of7-1k.attest-slot"
            assert (CELL in entry["workloads"]) == shared
    (entry,) = [c for c in man["configs"] if c["name"] == "dv-5of7-1k"]
    cfg = _config()
    assert cfg["source"] == entry["source"] and sorted(cfg["reduced"]) == entry["reduced"]
    assert len(entry["source"]) <= 200 and "createcluster.go" in entry["source"]


def test_the_configuration_is_dv_4of7_1k_but_for_its_threshold():
    cfg, base = _config(), _config("dv-4of7-1k")
    assert (cfg["operators"], cfg["threshold"], cfg["validators"]) == (7, 5, 1000)
    n = cfg["operators"]
    assert cfg["threshold"] == n - (n - 1) // 3  # upstream's default (cmd/cli.py)
    for key in ("node", "env", "programs", "reduced", "key_table_keys", "slots_per_epoch",
                "slot_duration_s", "keystore_kdf_c", "duty_types"):
        assert cfg[key] == base[key], key
    assert "threshold" not in cfg["assumed"]  # 5 is the source's, not hand-set
    assert cfg["guarantees"]["every_duty_completes_with_exactly_t_partials"] is True
    assert "not exercised" in cfg["guarantees"]["forged_partial_set_rejected"]


@pytest.mark.parametrize("seed", [1, 3000000007, 2**31 + 12345])
def test_the_mix_lands_on_the_programs_the_configuration_lists(seed):
    cfg = _config()
    plan = T.make_plan(cfg, _mix(), seed)
    T.check_programs(plan, cfg)
    assert plan.silent == (2, 5) and plan.senders() == cfg["threshold"] == 5
    lanes = sorted({plan.duties_in(p) * plan.senders() for p in range(32)})
    assert lanes == [155, 160]  # bucket 256, as dv-4of7-1k's 217-224
    assert plan.fault.kind == "none" and not any(
        plan.forged(slot, idx, 2) for slot in range(3) for idx in range(1, 8))


@pytest.mark.parametrize("change", [
    {"silent_operators": [2, 5, 6]},
    {"fault": {"kind": "flip_byte", "operator": "last", "slots": "last", "partials": 1}},
    {"fault": {"kind": "wrong_key", "operator": 3, "slots": "all", "partials": 1}},
], ids=["third-silent", "forger-last-slot", "forger-wrong-key"])
def test_a_mix_below_quorum_is_refused_before_boot(change):
    with pytest.raises(T.TrafficError, match="fewer than t honest"):
        T.make_plan(_config(), dict(_mix(), **change), 7)


def test_the_same_mix_on_the_healthy_threshold_would_need_no_new_program():
    """Why the cell needed a configuration of its own: on 4-of-7 two silent
    operators leave one share to spare, and the program list is the same."""
    base = _config("dv-4of7-1k")
    plan = T.make_plan(base, _mix(), 7)
    T.check_programs(plan, base)
    assert plan.senders() - base["threshold"] == 1


# -- the rehearsal ------------------------------------------------------------------


def _verify_flushes(extra):
    return [f for f in extra["flushes"] if f["verify_jobs"]]


@pytest.fixture(scope="module")
def rehearsal():
    """The rehearsal runs on the wall clock with windows of 50-75 ms: on
    a loaded CPU a set can trail its wave past the timer (a third verify
    flush), or the timer can run out while the last set is still
    decoding. Neither is what these tests are about, so such a run is
    made again, twice at most."""
    for _attempt in range(3):
        proc = subprocess.run(
            [sys.executable, str(REPO / "benchmark/tests/rehearse_nodedown.py")],
            capture_output=True, text=True, timeout=240, cwd=str(REPO))
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines = proc.stdout.strip().splitlines()
        line, extra = json.loads(lines[-2]), json.loads(lines[-1])
        closes = [(f["window_closed_by"] == "complete", f["sets_seen"])
                  for f in _verify_flushes(extra)]
        if closes == [(False, 3), (True, 3)]:
            break
    return line, extra


def test_every_duty_completes_at_bare_quorum(rehearsal):
    line, _extra = rehearsal
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["checks"].values())


def test_every_verify_window_falls_to_its_timer_one_set_short(rehearsal):
    """Until ISSUE 33 every verify window did. Now the FIRST does (the
    silent operator sent nothing before either, but before any wave the
    roster awaits all n); the second awaits the three that sent in the
    first and closes whole on them, one set short of n as before."""
    _line, extra = rehearsal
    verify = _verify_flushes(extra)
    recombine = [f for f in extra["flushes"] if not f["verify_jobs"]]
    assert len(verify) == len(recombine) == 2  # two slots, two flushes a wave
    first, second = verify
    assert first["window_closed_by"] in ("timer", "deadline")
    assert second["window_closed_by"] == "complete"
    for f in verify:
        assert (f["sets_expected"], f["sets_seen"], f["verify_jobs"]) == (4, 3, 3)
    for f in recombine:
        assert f["window_closed_by"] == "complete" and f["recombine_jobs"] == 1
        assert f["sets_expected"] is None
    # the short first wave fed the controller: the second verify window
    # was armed longer, and closing whole it fed the controller nothing
    assert second["window"] == pytest.approx(1.5 * first["window"])
    assert recombine[1]["window"] == pytest.approx(second["window"])
    windows = [s["attrs"] for s in extra["spans"]
               if s["name"] == "cryptoplane.window" and not s["attrs"].get("shared")]
    assert [(w["sets_expected"], w["sets_seen"], w["sets_awaited"], w["closed_by"] == "complete")
            for w in windows if w["verify_jobs"]] == [(4, 3, 4, False), (4, 3, 3, True)]


def test_every_aggregate_is_made_from_the_only_t_partials_there_are(rehearsal):
    line, extra = rehearsal
    aggregates = [s for s in extra["spans"] if s["name"] == "sigagg.aggregate"]
    assert len(aggregates) == 2
    assert sum(a["attrs"]["pubkeys"] for a in aggregates) == line["attempted"]
    # nothing to spare: the duty's verify window (same trace) saw as many
    # sets as its aggregates were made from
    seen = {w["trace_id"]: w["attrs"]["sets_seen"] for w in extra["spans"]
            if w["name"] == "cryptoplane.window" and w["attrs"]["verify_jobs"]}
    assert [(a["attrs"]["partials"], seen[a["trace_id"]]) for a in aggregates] == [(3, 3)] * 2


def test_a_traced_run_prints_the_two_new_metrics(rehearsal):
    line, _extra = rehearsal
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["sets_short_per_wave"] == 1.0
    assert line["metrics"]["sets_short_per_wave"]["unit"] == "count"
    # the verify windows alone, where `window_wait_s` is a middle of them
    # and the recombine windows that waited for nothing: the first waited
    # out its 50 ms, the second (ISSUE 33) only for its three sets
    assert 0.025 <= m["window_wait_s.verify"] < 2 and m["window_wait_s"] < m["window_wait_s.verify"]
    assert m["flushes_per_wave"] == 2.0
