"""The readers of the node's own spans (benchmark/nodespans.py and the
readers built on it), on a synthetic span forest and on the recorded trace
tests/data/tiny.xplane.pb: the innermost-span rule, self time with
overlapping children, `shared` copies counted once, the idle causes summing
to `window_s - busy_s`, and nothing read where the ring is not one whole
node's.

    python -m pytest benchmark/tests/test_nodespans.py -q -p no:cacheprovider
"""

from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import manifest as M, nodespans, tracered  # noqa: E402
from benchmark.tests import helpers  # noqa: E402
# the duty kinds' tests: tier-1 collects tests/ alone, and this file's tests
# come in there through tests/test_node_spans.py's `import *` — so do these
from benchmark.tests.test_duties import *  # noqa: E402,F401,F403
from benchmark.tests.test_starts import *  # noqa: E402,F401,F403
from charon_tpu.app import tracer  # noqa: E402

TRACE = "c" * 32
SLOT = 12.0
T0 = 1000.0  # the traced slot's start; its trigger is due at T0 + 4


def span(name, start, end, sid, parent="", trace=TRACE, **attrs):
    return tracer.Span(trace, sid, parent, name, T0 + start, T0 + end, dict(attrs))


def wave():
    """One attester wave as the node records it, seconds from the slot's
    start: decided 4.2, the VC's submission 4.4 and a peer's set 4.5 ride
    one verify flush (window 4.6-4.9, decode/pack to 5.3, device to 6.4)."""
    duty = {"duty": "7/attester", "slot": 7}
    flush = [  # (name, start, end): bridged under each submitter
        ("cryptoplane.window", 4.6, 4.9), ("cryptoplane.flush", 4.45, 6.4)]
    stages = [("cryptoplane.decode", 4.45, 4.6), ("cryptoplane.decode", 4.7, 4.8),
              ("cryptoplane.pack", 4.9, 5.3), ("cryptoplane.device", 5.3, 6.4)]
    out = [
        span("fetcher.fetch", 4.0, 4.25, "ff", **duty),
        span("consensus.propose", 4.01, 4.25, "p0", "ff", **duty),
        span("qbft.instance", 4.02, 4.2, "q0", "p0", **duty),
        span("dutydb.store", 4.2, 4.25, "d0", "p0", **duty),
        span("qbft.deliver", 4.05, 4.06, "q1", **duty),
        span("vapi.submit", 4.4, 6.5, "v0", **duty),
        span("cryptosvc.queue", 4.42, 4.45, "vq", "v0"),
        span("parsigex.receive", 4.5, 6.6, "r0", **duty),
        span("parsigex.verify", 4.5, 6.45, "r1", "r0"),
        span("cryptosvc.queue", 4.55, 4.6, "rq", "r1"),
        span("parsigdb.store_external", 6.45, 6.6, "r2", "r0", **duty),
        # a duty from before the window that never decided, cancelled at
        # its deadline slots later, and a duty the traffic never completes:
        # both open all the while, neither the window's attester duty
        span("qbft.instance", -20.0, 10.0, "st", trace="d" * 32, duty="5/attester", slot=5),
        span("fetcher.fetch", 0.0, 16.0, "a0", trace="a" * 32, duty="7/prepare_aggregator",
             slot=7),
    ]
    for n, parent in enumerate(("v0", "r1")):
        shared = {"shared": True} if n else {}
        for name, a, b in flush:
            out.append(span(name, a, b, f"{name[12]}{n}", parent, **shared))
        for k, (name, a, b) in enumerate(stages):
            out.append(span(name, a, b, f"s{n}{k}", f"f{n}", **shared))
    return out


class Run:
    slot_duration = SLOT
    window = (T0, T0 + SLOT)
    slots = [7]
    duty_types = ("attester",)  # as serve.RunData says its mix's kinds
    trace = None

    def in_window(self, ts):
        return self.window[0] <= ts < self.window[1] + SLOT

    def waves(self):
        return [{"slot": 7, "duties": 2, "due": T0 + 4.0, "last_done": T0 + 7.0}]


@pytest.fixture()
def node(monkeypatch):
    """One registered node whose ring holds the synthetic wave."""
    t = tracer.Tracer()
    for s in wave():
        t.record(s)
    monkeypatch.setattr(tracer, "_NODE_TRACERS", {0: t})
    monkeypatch.setattr(nodespans, "_last", (None, None))
    return t


def read(name, run):
    man = M.load_manifest(REPO)
    entry = next(m for m in man["per_layer"] if m["name"] == name)
    metric = M._metric(M.bench_dir(REPO, man), entry)
    return M.load_reader(REPO, man, metric.reader)(run, **metric.params)


def test_self_time_is_the_span_minus_what_its_children_cover():
    spans = wave()
    children = nodespans.by_parent(spans)
    by_id = {s.span_id: s for s in spans}
    rel = lambda ivs: [(round(a - T0, 3), round(b - T0, 3)) for a, b in ivs]  # noqa: E731
    # overlapping children (queue, flush, window) are a union, not a sum
    assert rel(nodespans.self_intervals(by_id["v0"], children)) == [(4.4, 4.42), (6.4, 6.5)]
    # a child that starts before its parent is clipped to it
    assert rel(nodespans.self_intervals(by_id["r1"], children)) == [(6.4, 6.45)]
    assert rel(nodespans.self_intervals(by_id["r0"], children)) == []
    assert rel(nodespans.self_intervals(by_id["q0"], children)) == [(4.02, 4.2)]


def test_the_span_metrics_on_the_synthetic_wave(node):
    run = Run()
    # vapi.submit 0.02 + 0.1, parsigex.verify 0.05 (inside vapi.submit's
    # 6.4-6.5: the union counts those instants once), parsigex.receive 0
    assert read("entry_self_s", run) == pytest.approx(0.12)
    assert read("qbft_decide_s", run) == pytest.approx(0.18)
    assert read("svc_queue_s", run) == pytest.approx(0.04)  # median of 0.03, 0.05
    # the window is bridged twice; the `shared` copy is no second flush
    assert read("window_wait_s", run) == pytest.approx(0.3)
    assert read("agg_bcast_self_s", run) is None  # nothing aggregated yet: left out


def test_an_idle_instant_gets_the_cause_nearest_the_device(node):
    spans = nodespans.duty_spans(Run(), list(node.spans), ["attester"])
    assert all(s.trace_id == TRACE for s in spans)  # the parked fetch is another duty's
    seg = nodespans.cause_segments(spans, [T0 + 4.0], [T0], T0, T0 + 7.0)
    assert seg[0][0] == T0 and seg[-1][1] == T0 + 7.0
    assert all(a[1] == b[0] for a, b in zip(seg, seg[1:]))  # a partition

    def cause(at):
        return next(c for lo, hi, c in seg if lo <= T0 + at < hi)

    assert cause(2.0) == "pre_trigger"
    assert cause(4.005) == "other"  # fetcher.fetch alone
    assert cause(4.015) == cause(4.1) == cause(4.055) == "consensus"
    assert cause(4.22) == "other"  # dutydb.store under the propose edge
    assert cause(4.3) == "awaiting_input"  # decided; the VC has not come back
    assert cause(4.41) == "entry" and cause(4.43) == "window"  # the tenant's queue
    assert cause(4.5) == "pack"  # a decode stretch beats the entry spans beside it
    assert cause(4.65) == "window" and cause(4.75) == "pack" and cause(4.85) == "window"
    assert cause(5.0) == "pack" and cause(6.0) == "other"  # device span: not idle time anyway
    assert cause(6.42) == "entry" and cause(6.55) == "entry"
    assert cause(6.8) == "awaiting_input"


def test_the_idle_causes_sum_to_window_minus_busy(node):
    run = Run()
    run.trace = tracered.reduce_file(
        str(helpers.RECORDED), T0 + 4.1, helpers.RECORDED_WINDOW_S)
    total = nodespans.idle_seconds(run)
    assert set(total) == set(nodespans.ORDER + nodespans.NO_SPAN)
    assert sum(total.values()) == pytest.approx(run.trace.window_s - run.trace.busy_s, abs=1e-9)
    # the recorded 0.7-0.9 s from 4.1: consensus to 4.2, other to 4.25, waiting
    # to 4.4, ..., the window from 4.8 to 4.9 and the pack from there
    w = helpers.RECORDED_WINDOW_S
    assert 0.7 < w < 0.9
    assert total["consensus"] == pytest.approx(0.1, abs=2e-3)
    assert total["awaiting_input"] == pytest.approx(0.15, abs=2e-3)
    assert total["pack"] == pytest.approx(0.15 + 0.1 + max(0.0, w - 0.8), abs=2e-3)
    assert total["window"] == pytest.approx(0.03 + 0.1 + min(w, 0.8) - 0.7, abs=2e-3)
    assert total["pre_trigger"] == 0.0
    for name in ("consensus", "awaiting_input", "entry", "window", "pack"):
        assert read(f"idle_s.{name}", run) == total[name]
    run.trace = None  # --trace 0, or a reader with nothing to read
    nodespans._last = (None, None)
    assert read("idle_s.pack", run) is None


@pytest.mark.parametrize("state", ["no node", "two nodes", "wrapped ring", "no registry"])
def test_a_ring_that_is_not_one_whole_nodes_is_not_read(monkeypatch, node, state):
    if state == "no node":
        monkeypatch.setattr(tracer, "_NODE_TRACERS", {})
    elif state == "two nodes":
        monkeypatch.setattr(tracer, "_NODE_TRACERS", {0: node, 1: tracer.Tracer()})
    elif state == "wrapped ring":
        small = tracer.Tracer(capacity=8)
        for s in wave():
            small.record(s)
        assert small.evicted > 0
        monkeypatch.setattr(tracer, "_NODE_TRACERS", {0: small})
    else:  # the program before it had a tracer per node
        monkeypatch.delattr(tracer, "node_tracers")
    assert nodespans.node_spans() is None
    run = Run()
    run.trace = tracered.reduce_file(
        str(helpers.RECORDED), T0 + 4.1, helpers.RECORDED_WINDOW_S)
    for name in ("entry_self_s", "qbft_decide_s", "svc_queue_s", "window_wait_s",
                 "agg_bcast_self_s", "idle_s.window"):
        assert read(name, run) is None


def test_the_recorded_trace_keeps_the_structure_the_reducer_reads():
    """tests/data/tiny.xplane.pb, as tests/record_trace.py records it on a
    v5e through tracered's own session and options (PR 34): the device
    plane with its `XLA Modules` and `XLA Ops` lines, modules named
    jit_<function>(<id>), operations by their instruction text (which is the
    program's own, not the HLO proto's: none rides along)."""
    import dataclasses

    from jax.profiler import ProfileData

    blob = helpers.RECORDED.read_bytes()
    planes = {p.name: p for p in ProfileData.from_serialized_xspace(blob).planes}
    # libtpu names a `/host:metadata` plane whatever the options say; under
    # these it is empty (with HLO protos the two jits' made the file 18,528 bytes)
    assert len(blob) < 15000
    assert [n for n in planes if n.startswith("/device:TPU")] == ["/device:TPU:0"]
    lines = {ln.name: list(ln.events) for ln in planes["/device:TPU:0"].lines}
    assert {tracered.MODULE_LINE, tracered.OPS_LINE} <= set(lines)
    modules = sorted(lines[tracered.MODULE_LINE], key=lambda e: e.start_ns)
    assert [e.name.split("(")[0] for e in modules] == [
        "jit_verify_like", "jit_recombine_like"] * 3
    assert all(e.name.split("(")[1].rstrip(")").isdigit() for e in modules)
    ops = lines[tracered.OPS_LINE]
    assert len(ops) == 12 and all(e.name.startswith("%") and " = " in e.name for e in ops)
    assert all(e.duration_ns >= 0 and e.start_ns > 0 for e in ops + modules)
    # every operation lies inside a module, and inside the recorded window
    spans = [(m.start_ns, m.start_ns + m.duration_ns) for m in modules]
    assert all(any(a <= e.start_ns and e.start_ns + e.duration_ns <= b + 1 for a, b in spans)
               for e in ops)
    assert spans[-1][1] * 1e-9 < helpers.RECORDED_WINDOW_S
    # what a run reads from memory is what the file holds
    read = tracered.reduce_bytes(blob, T0, helpers.RECORDED_WINDOW_S)
    filed = tracered.reduce_file(str(helpers.RECORDED), T0, helpers.RECORDED_WINDOW_S)
    assert dataclasses.asdict(read) == dataclasses.asdict(filed)
    assert read.events == 12 and read.devices == 1 and read.stop_s is None
    assert read.planes == {"/device:TPU:0": {  # every line that holds anything
        tracered.MODULE_LINE: 6, tracered.OPS_LINE: 12, "Async XLA Ops": 3}}


def test_a_session_of_the_harness_ends_in_bytes_and_leaves_no_file(tmp_path, monkeypatch):
    """tracered.start / stop_bytes on the CPU: the profiler's own session
    under the one set of options (python and host tracing off, no HLO
    proto), ended into a serialised XSpace that holds no `/host:metadata`
    plane and — there being no device here — nothing the reducer reads;
    nothing is written under the working directory."""
    import jax
    from jax.profiler import ProfileData

    opts = tracered.options(jax)
    assert (opts.python_tracer_level, opts.host_tracer_level, opts.enable_hlo_proto) == (
        0, 0, False)
    assert dict(opts.advanced_configuration) == {}
    monkeypatch.chdir(tmp_path)
    handle = tracered.start(jax)
    assert handle["wall"] > 0
    jax.numpy.ones((8, 8)).sum().block_until_ready()
    blob = tracered.stop_bytes(handle)
    assert isinstance(blob, bytes) and "session" not in handle
    names = [p.name for p in ProfileData.from_serialized_xspace(blob).planes]
    assert "/host:metadata" not in names and not any(n.startswith("/device:") for n in names)
    with pytest.raises(RuntimeError, match="no device operation"):
        tracered.reduce_bytes(blob, handle["wall"], 1.0)
    assert list(tmp_path.iterdir()) == []


def test_the_new_metrics_are_files_and_entries_like_the_old_ones():
    man = M.load_manifest(REPO)
    assert M.validate(man) == []
    new = [m for m in man["per_layer"] if m["source"] in ("program_span", "device_trace")
           and (m["name"].startswith("idle_s.") or m["layer"] in ("Entry", "Tenant service")
                or m["name"] in ("qbft_decide_s", "agg_bcast_self_s", "window_wait_s"))]
    # appended in one block, in this order; what later PRs append comes after it
    first = man["per_layer"].index(new[0])
    assert len(new) == 10 and man["per_layer"][first:first + 10] == new
    assert first >= 7  # after PR 25's seven
    cells = [w["name"] for w in man["workloads"]]
    for m in new:
        assert m["moves"] == "duty_p50_s" and m["workloads"] == cells
        assert M._metric(M.bench_dir(REPO, man), m).reader in (
            "span_self", "span_duration", "idle_cause")


# -- a mix of several kinds (ISSUE 43): any duty's innermost span, (slot, kind) waves --


def _two_kinds():
    """The synthetic attester wave and, in the same slot, a sync-message
    duty whose verify flush holds a window open from 4.25 to 4.38 — where
    the attester duty has nothing open — and a duty that validator clients
    start: no fetch, no consensus, a submission at the slot's start."""
    sync = {"duty": "7/sync_message", "slot": 7}
    reg = {"duty": "7/builder_registration", "slot": 7}
    return wave() + [
        span("vapi.submit", 4.25, 4.4, "sv", trace="e" * 32, **sync),
        span("cryptoplane.window", 4.25, 4.38, "sw", "sv", trace="e" * 32),
        span("vapi.submit", 0.0, 0.3, "bv", trace="b" * 32, **reg),
        span("cryptosvc.queue", 0.1, 0.2, "bq", "bv", trace="b" * 32),
    ]


def test_a_one_kind_run_reads_the_idle_causes_it_read_before(node):
    """On tests/data/tiny.xplane.pb, the seconds the parent's reader gave
    this run when the metric files named the kind (`"duty": "attester"`):
    they name none any more, and the run says its own."""
    run = Run()
    run.trace = tracered.reduce_file(
        str(helpers.RECORDED), T0 + 4.1, helpers.RECORDED_WINDOW_S)
    total = nodespans.idle_seconds(run)
    w = helpers.RECORDED_WINDOW_S
    assert {c: round(v, 3) for c, v in total.items() if v} == {
        "consensus": 0.1, "other": 0.05, "awaiting_input": 0.15,
        "entry": round(total["entry"], 3), "window": round(0.13 + min(w, 0.8) - 0.7, 3),
        "pack": round(0.25 + max(0.0, w - 0.8), 3)}
    assert read("idle_s.window", run) == total["window"]
    man = M.load_manifest(REPO)
    for entry in man["per_layer"]:
        if entry["name"].startswith("idle_s."):
            metric = M._metric(M.bench_dir(REPO, man), entry)
            assert metric.params == {"cause": entry["name"].split(".", 1)[1]}


def test_an_idle_instant_takes_the_innermost_span_of_any_of_the_windows_duties():
    spans = _two_kinds()
    run = Run()
    one = nodespans.duty_spans(run, spans, ["attester"])
    both = nodespans.duty_spans(run, spans, ("attester", "sync_message"))
    assert len(both) == len(one) + 2
    starts = [T0]

    def cause(mine, at):
        seg = nodespans.cause_segments(mine, [T0 + 4.0], starts, T0, T0 + 7.0)
        return next(c for lo, hi, c in seg if lo <= T0 + at < hi)

    # decided at 4.25, the VC back at 4.4: the attester duty has nothing open,
    # the sync duty's window is — the two-kind cell's view until PR 43 said
    # `awaiting_input` there
    assert cause(one, 4.3) == "awaiting_input" and cause(both, 4.3) == "window"
    assert cause(both, 4.39) == "entry"  # the sync submission, its window closed
    for at in (2.0, 4.1, 4.5, 4.75, 6.8):  # elsewhere the nearer cause wins as before
        assert cause(both, at) == cause(one, at)
    # a kind that validator clients start: no fetch, no consensus span is
    # assumed; its request is due at the slot's start, so nothing of the slot
    # is `pre_trigger`
    every = nodespans.duty_spans(run, spans, ("attester", "builder_registration"))
    seg = nodespans.cause_segments(every, [T0], starts, T0, T0 + 7.0)
    at = lambda t: next(c for lo, hi, c in seg if lo <= T0 + t < hi)  # noqa: E731
    assert (at(0.05), at(0.15), at(0.25), at(2.0)) == ("entry", "window", "entry",
                                                       "awaiting_input")
    assert at(4.1) == "consensus"  # the attester's, as ever


def test_flushes_per_wave_counts_a_wave_a_slot_and_kind():
    from benchmark.serve import DutyRecord, RunData

    man = M.load_manifest(REPO)
    read_flushes = M.load_reader(REPO, man, "flushes_per_wave")
    run = RunData(window=(1000.0, 1036.0), slots=[7, 8, 9])
    stat = types.SimpleNamespace()
    run.flushes = [(1004.0 + 12 * k + i, stat) for k in range(3) for i in (0.5, 1.5)]
    run.flushes.append((990.0, stat))  # before the window
    assert read_flushes(run) is None  # no duty, no wave: nothing to read
    for slot in run.slots:
        run.duties += [DutyRecord("attester", slot, v, "0x", 1000.0) for v in range(3)]
    assert read_flushes(run) == 2.0  # one kind: (slot, kind) waves ARE the slots
    # a second kind every slot: four flushes a slot read 2, where they read 4
    run.flushes += [(1004.0 + 12 * k + i, stat) for k in range(3) for i in (0.7, 1.7)]
    for slot in run.slots:
        run.duties += [DutyRecord("sync_message", slot, v, "0x", 1000.0) for v in range(5)]
    assert read_flushes(run) == 2.0
    # a kind with a duty in ONE slot of the three: seven waves, fourteen flushes
    run.duties.append(DutyRecord("registration", 8, 0, "0x", 1000.0))
    run.flushes += [(1012.2, stat), (1013.1, stat)]
    assert read_flushes(run) == 2.0
    run.flushes.append((1013.5, stat))  # a wave that split
    assert read_flushes(run) == pytest.approx(15 / 7)
    assert read_flushes(RunData(window=(1000.0, 1036.0), slots=[7])) is None
    # the one-kind cells list the metric; the two-kind cell's tier-1 test
    # (tests/test_two_kinds.py) still holds it out: PERF.md §7
    entry = next(m for m in man["per_layer"] if m["name"] == "flushes_per_wave")
    assert len(entry["workloads"]) == 4
