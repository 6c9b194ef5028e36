"""Flight recorder unit tests (ISSUE 19): bounded-memory storm
isolation, concurrent writers, sanitization, crash-dump handlers,
cross-node merge and the text timeline. Jax-free by design — the
recorder is app-layer stdlib.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import sys
import threading
import time

import pytest

from charon_tpu.app import flightrec
from charon_tpu.app.flightrec import (
    CATEGORIES,
    DEFAULT_CAPACITY,
    EVENT_KINDS,
    FlightRecorder,
    install_crash_handlers,
    merge_jsonl,
    render_timeline,
)
from charon_tpu.testutil.waiting import wait_until


def test_flush_storm_cannot_evict_rare_categories():
    rec = FlightRecorder(capacity=16)
    # three rare byzantine detections land first...
    for i in range(3):
        rec.record("byzantine", "qbft_equivocation", peer=i + 1)
    # ...then a 10k-event flush storm
    for i in range(10_000):
        rec.record("flush", "flush", jobs=1, lanes=4)
    # the storm evicted only its own category
    assert len(rec.events(category="byzantine")) == 3
    assert len(rec.events(category="flush")) == 16
    assert rec.recorded_total["flush"] == 10_000
    assert rec.dropped_total["flush"] == 10_000 - 16
    assert rec.dropped_total["byzantine"] == 0


def test_concurrent_writers_keep_sequence_dense():
    rec = FlightRecorder(capacity=100_000)
    n_threads, per_thread = 8, 500
    cats = list(CATEGORIES)

    def writer(tid: int) -> None:
        for i in range(per_thread):
            rec.record(cats[(tid + i) % len(cats)], "stress", i=i, tid=tid)

    threads = [
        threading.Thread(target=writer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = rec.events()
    assert len(events) == n_threads * per_thread
    seqs = sorted(e.seq for e in events)
    # every append got a unique, dense sequence number
    assert seqs == list(range(1, n_threads * per_thread + 1))


def test_sanitization_blocks_structured_values():
    rec = FlightRecorder()

    class Secretish:
        pass

    rec.record(
        "lifecycle",
        "start",
        obj=Secretish(),
        big="x" * 10_000,
        pairs=[("tenant-a", 4), ("tenant-b", 2)],
        many=list(range(100)),
        ok=7,
    )
    (ev,) = rec.events(category="lifecycle")
    assert ev.fields["obj"] == "<Secretish>"
    assert len(ev.fields["big"]) <= 203 and ev.fields["big"].endswith("...")
    assert ev.fields["pairs"] == [["tenant-a", 4], ["tenant-b", 2]]
    assert len(ev.fields["many"]) == 16
    assert ev.fields["ok"] == 7
    # the event round-trips through JSON (the dump contract)
    json.dumps(ev.to_dict(node="n0"))


def test_unknown_category_coerced_not_raised():
    rec = FlightRecorder()
    rec.record("no-such-category", "boom", x=1)
    (ev,) = rec.events(category="lifecycle")
    assert ev.kind == "boom"
    assert ev.fields["miscategorized"] == "no-such-category"


def test_event_filters_and_limit():
    rec = FlightRecorder()
    rec.record("tenant", "shed", tenant="a", slot=5, reason="queue_lanes")
    rec.record("tenant", "shed", tenant="b", slot=5, reason="queue_jobs")
    rec.record("duty", "duty_ok", tenant="a", slot=6)
    assert len(rec.events(tenant="a")) == 2
    assert len(rec.events(slot=5)) == 2
    assert len(rec.events(category="tenant", tenant="b")) == 1
    newest = rec.events(limit=1)
    assert len(newest) == 1 and newest[0].kind == "duty_ok"
    assert len(rec) == 3


def test_observer_fires_and_exceptions_swallowed():
    seen = []

    def observer(category, kind):
        seen.append((category, kind))
        raise RuntimeError("observer bug")

    rec = FlightRecorder(observer=observer)
    rec.record("flush", "flush")  # must not raise
    assert seen == [("flush", "flush")]


def test_dump_header_and_merge_dedup(tmp_path):
    rec1 = FlightRecorder(node="node1")
    rec2 = FlightRecorder(node="node2")
    rec1.record("remote", "failover", tenant="c", reason="io")
    time.sleep(0.01)
    rec2.record("remote", "server_shed", tenant="c", reason="abort")
    p1, p2 = str(tmp_path / "n1.jsonl"), str(tmp_path / "n2.jsonl")
    assert rec1.dump_jsonl(p1, trigger="demand") == 1
    assert rec2.dump_jsonl(p2) == 1
    assert rec1.dumps_total == {"demand": 2} or rec1.dumps_total["demand"] >= 1

    header = json.loads(open(p1).readline())
    assert header["schema"] == flightrec.SCHEMA_VERSION
    assert header["node"] == "node1"

    # merging the same file twice dedups by (node, seq); wall-clock
    # order puts node1's earlier event first
    merged = merge_jsonl([p1, p2, p1])
    assert [e["node"] for e in merged] == ["node1", "node2"]
    assert merged[0]["kind"] == "failover"
    assert merged[1]["kind"] == "server_shed"

    text = render_timeline(merged)
    assert "failover" in text and "server_shed" in text
    assert "tenant=c" in text and "node1" in text

    # unreadable paths are skipped, not fatal
    assert merge_jsonl([str(tmp_path / "missing.jsonl"), p1])


def test_dump_is_atomic(tmp_path):
    rec = FlightRecorder(node="n")
    rec.record("lifecycle", "start")
    path = str(tmp_path / "dump.jsonl")
    rec.dump_jsonl(path)
    assert os.path.exists(path)
    # no tmp droppings left behind
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_crash_handlers_dump_and_chain(tmp_path):
    rec = FlightRecorder(node="crashy")
    rec.record("lifecycle", "start")
    path = str(tmp_path / "crash.jsonl")
    prev_calls = []
    prev_hook = sys.excepthook
    sys.excepthook = lambda *a: prev_calls.append("sys")
    uninstall = install_crash_handlers(rec, path)
    try:
        # unhandled main-thread exception -> dump + chained prev hook
        sys.excepthook(RuntimeError, RuntimeError("boom"), None)
        assert prev_calls == ["sys"]
        merged = merge_jsonl([path])
        kinds = [e["kind"] for e in merged]
        assert "crash_dump" in kinds
        header = json.loads(open(path).readline())
        assert header["trigger"] == "crash"

        # unhandled worker-thread exception -> its own dump trigger
        def die():
            raise RuntimeError("thread boom")

        t = threading.Thread(target=die)
        t.start()
        t.join()
        header = json.loads(open(path).readline())
        assert header["trigger"] == "thread-crash"
    finally:
        uninstall()
        sys.excepthook = prev_hook
    assert sys.excepthook is prev_hook


@pytest.mark.skipif(
    threading.current_thread() is not threading.main_thread(),
    reason="signal handlers need the main thread",
)
def test_sigterm_dumps_and_chains(tmp_path):
    rec = FlightRecorder(node="term")
    rec.record("lifecycle", "start")
    path = str(tmp_path / "term.jsonl")
    chained = []
    prev = signal.signal(signal.SIGTERM, lambda *a: chained.append("prev"))
    uninstall = install_crash_handlers(rec, path)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        # the signal lands at a bytecode boundary
        asyncio.run(wait_until(lambda: chained, "the chained SIGTERM handler"))
        assert chained == ["prev"]
        header = json.loads(open(path).readline())
        assert header["trigger"] == "sigterm"
    finally:
        uninstall()
        signal.signal(signal.SIGTERM, prev)


def test_schema_constants_consistent():
    # every declared kind category exists, capacity default sane
    assert set(EVENT_KINDS) == set(CATEGORIES)
    assert DEFAULT_CAPACITY >= 128
    rec = FlightRecorder()
    for cat, kinds in EVENT_KINDS.items():
        assert kinds, f"category {cat} declares no kinds"


def test_hook_adapters_chain_and_record():
    rec = FlightRecorder(node="n")
    inner_calls = []

    th = flightrec.tenant_hook(rec, inner=lambda k, t, **f: inner_calls.append(k))
    th("shed", "tenant-a", reason="queue_lanes", lanes=9)
    th("dispatch", "tenant-a", lanes=9)  # telemetry: inner only
    assert [e.kind for e in rec.events(category="tenant")] == ["shed"]
    assert inner_calls == ["shed", "dispatch"]

    rh = flightrec.remote_hook(rec, "tenant-a", addr="10.0.0.9:9000")
    rh("failover", reason="io", lanes=128)
    (ev,) = rec.events(category="remote")
    assert ev.fields["addr"] == "10.0.0.9:9000"
    assert ev.fields["reason"] == "io"

    sh = flightrec.server_hook(rec)
    sh("shed", "tenant-b", reason="breaker")
    kinds = [e.kind for e in rec.events(category="remote")]
    assert "server_shed" in kinds

    bh = flightrec.byzantine_hook(rec, inner=lambda p, k: inner_calls.append(k))
    bh(3, "qbft_equivocation", "two proposals in round 2")
    (bev,) = rec.events(category="byzantine")
    assert bev.fields["peer"] == 3
    assert "two proposals" in bev.fields["detail"]
    assert inner_calls[-1] == "qbft_equivocation"

    qh = flightrec.quarantine_hook(rec)
    qh(2, 30.0)
    (qev,) = rec.events(category="quarantine")
    assert qev.kind == "peer_muted" and qev.fields["peer"] == 2

    ah = flightrec.autotune_hook(rec)
    ah("decision", axis="msm", choice="windowed", source="profile")
    (aev,) = rec.events(category="autotune")
    assert aev.fields == {
        "axis": "msm", "choice": "windowed", "source": "profile"
    }


def test_stats_hook_records_flush_summary():
    rec = FlightRecorder(node="n")

    class Stats:
        jobs = 3
        lanes = 96
        flush_seconds = 0.012
        device_span = (10.0, 10.008)
        window = 0.02
        fallback = False
        decode_mode = "device"
        tenant_lanes = (("tenant-a", 64), ("tenant-b", 32))

    inner = []
    hook = flightrec.stats_hook(rec, inner=inner.append)
    hook(Stats())
    (ev,) = rec.events(category="flush")
    assert ev.kind == "flush"
    assert ev.fields["jobs"] == 3 and ev.fields["lanes"] == 96
    assert ev.fields["device_seconds"] == pytest.approx(0.008)
    assert ev.fields["tenants"] == ["tenant-a", "tenant-b"]
    assert len(inner) == 1

    # a shape change degrades to flush_unparsed, never an exception
    hook(object())
    kinds = [e.kind for e in rec.events(category="flush")]
    assert kinds == ["flush", "flush_unparsed"]
    assert len(inner) == 2


def test_duty_hook_records_outcomes():
    rec = FlightRecorder()

    class Duty:
        slot = 42

        def __str__(self):
            return "attester/42"

    class Report:
        duty = Duty()
        success = False
        failed_step = "parsig_ex"
        reason = None
        trace_id = "abc123"

    flightrec.duty_hook(rec)(Report())
    (ev,) = rec.events(category="duty")
    assert ev.kind == "duty_failed"
    assert ev.slot == 42
    assert ev.fields["failed_step"] == "parsig_ex"
    assert ev.fields["trace_id"] == "abc123"


def test_evidence_registry_passes_detail_to_three_arg_hooks():
    from charon_tpu.core.evidence import EvidenceRegistry

    rec = FlightRecorder()
    two_arg = []

    # 3-arg flightrec adapter receives the detail
    reg = EvidenceRegistry(hook=flightrec.byzantine_hook(rec))
    reg.record(5, "parsig_conflict", detail="double-signed slot 9")
    (ev,) = rec.events(category="byzantine")
    assert ev.fields["detail"] == "double-signed slot 9"

    # legacy 2-arg hooks keep working unchanged
    reg2 = EvidenceRegistry(hook=lambda peer, kind: two_arg.append((peer, kind)))
    reg2.record(1, "qbft_flood", detail="ignored")
    assert two_arg == [(1, "qbft_flood")]
