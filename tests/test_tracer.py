"""Workflow tracing (ref: app/tracer/trace.go, core/tracing.go)."""

from __future__ import annotations

import asyncio

import pytest

from charon_tpu.app import tracer
from charon_tpu.core.types import Duty, DutyType
from charon_tpu.core.wire import tracing


def test_span_nesting_and_trace_propagation():
    t = tracer.Tracer()
    duty = Duty(slot=7, type=DutyType.ATTESTER)
    with tracer.span("outer", duty=duty, tracer=t) as outer:
        with tracer.span("inner", tracer=t) as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = t.dump()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    assert all(s["duration_us"] >= 0 for s in spans)


def test_duty_trace_id_deterministic_across_nodes():
    duty = Duty(slot=42, type=DutyType.PROPOSER)
    assert tracer.duty_trace_id(duty) == tracer.duty_trace_id(
        Duty(slot=42, type=DutyType.PROPOSER)
    )
    assert tracer.duty_trace_id(duty) != tracer.duty_trace_id(
        Duty(slot=43, type=DutyType.PROPOSER)
    )


def test_error_spans_marked():
    t = tracer.Tracer()
    with pytest.raises(ValueError):
        with tracer.span("boom", tracer=t):
            raise ValueError("nope")
    (s,) = t.dump()
    assert s["status"] == "error"
    assert "ValueError" in s["attrs"]["error"]


def test_tracing_wire_option_records_edges():
    t = tracer.Tracer()
    duty = Duty(slot=3, type=DutyType.ATTESTER)

    async def run():
        async def fetch(d, defs):
            return "fetched"

        wrapped = tracing(t)("fetcher.fetch", fetch)
        assert await wrapped(duty, {}) == "fetched"

    asyncio.run(run())
    (s,) = t.dump()
    assert s["name"] == "fetcher.fetch"
    assert s["trace_id"] == tracer.duty_trace_id(duty)
    assert s["attrs"]["duty"] == str(duty)


def test_jsonl_export(tmp_path):
    import json

    path = tmp_path / "traces.jsonl"
    t = tracer.Tracer(jsonl_path=str(path))
    with tracer.span("exported", tracer=t):
        pass
    t.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines and lines[0]["name"] == "exported"


def test_debug_traces_endpoint():
    import json
    import urllib.request

    from charon_tpu.app.metrics import ClusterMetrics, serve_monitoring

    async def run():
        t = tracer.Tracer()
        duty = Duty(slot=9, type=DutyType.ATTESTER)
        with tracer.span("edge", duty=duty, tracer=t):
            pass
        metrics = ClusterMetrics("0xdead", "test", "node0")
        server = await serve_monitoring("127.0.0.1", 0, metrics, tracer=t)
        port = server.sockets[0].getsockname()[1]

        def get(url):
            with urllib.request.urlopen(url) as resp:
                return json.loads(resp.read())

        spans = await asyncio.to_thread(
            get, f"http://127.0.0.1:{port}/debug/traces"
        )
        assert spans and spans[0]["name"] == "edge"
        filt = await asyncio.to_thread(
            get,
            f"http://127.0.0.1:{port}/debug/traces?trace_id="
            + tracer.duty_trace_id(duty),
        )
        assert len(filt) == 1
        none = await asyncio.to_thread(
            get, f"http://127.0.0.1:{port}/debug/traces?trace_id=" + "0" * 32
        )
        assert none == []
        server.close()
        await server.wait_closed()

    asyncio.run(run())


def test_a_wrapped_ring_reports_what_it_evicted():
    t = tracer.Tracer(capacity=4)
    for i in range(4):
        tracer.record_span(f"s{i}", "a" * 32, "", 1.0, 2.0, tracer=t)
    assert t.evicted == 0 and len(t.spans) == 4
    for i in range(3):
        tracer.record_span(f"late{i}", "a" * 32, "", 1.0, 2.0, tracer=t)
    # the ring keeps the newest four and says how many it dropped, so a
    # reader of a whole window can refuse it
    assert t.evicted == 3
    assert [s.name for s in t.spans] == ["s3", "late0", "late1", "late2"]


def test_node_tracer_registry_holds_each_nodes_own_tracer(monkeypatch):
    monkeypatch.setattr(tracer, "_NODE_TRACERS", {})
    a, b = tracer.Tracer(), tracer.Tracer()
    tracer.register_node_tracer(0, a)
    tracer.register_node_tracer(1, b)
    assert tracer.node_tracers() == {0: a, 1: b}
    rebuilt = tracer.Tracer()
    tracer.register_node_tracer(0, rebuilt)  # the index built again
    assert tracer.node_tracers()[0] is rebuilt
    assert a is not tracer.global_tracer()
    # a copy: a reader cannot unregister a node by accident
    tracer.node_tracers().clear()
    assert len(tracer.node_tracers()) == 2


def _flush_stats(**over):
    from charon_tpu.core.cryptoplane import FlushStats

    base = dict(
        jobs=3, lanes=12, flush_seconds=0.5, window=0.3, inflight=1,
        pad_lanes=4, padded_lanes=16, decode_queue_seconds=(),
        decode_spans=((10.0, 10.1), (10.05, 10.2), (10.6, 10.7)),
        pack_span=(10.8, 10.9), device_span=(10.9, 11.4),
        window_span=(10.2, 10.5), window_closed_by="timer",
    )
    base.update(over)
    return FlushStats(**base)


def test_plane_bridge_records_the_flush_under_every_submitting_span():
    """One duty's VC submission and a peer's set ride one flush in ONE
    trace: each submitting span gets the window and the stages as its
    children (its self time is what they do not cover); a copy beyond
    the first is `shared`, so a hook counts the physical stage once."""
    t = tracer.Tracer()
    seen = []
    t.hooks.append(lambda s: seen.append(s.name) if not s.attrs.get("shared") else None)
    tid = "b" * 32
    hook = tracer.plane_span_bridge(
        t, programs=lambda: ["verify_rlc_dec@16"])
    hook(_flush_stats(parents=((tid, "1" * 16), (tid, "2" * 16), (tid, "1" * 16))))
    by_parent: dict = {}
    for s in t.spans:
        by_parent.setdefault(s.parent_id, []).append(s)
    assert set(by_parent) >= {"1" * 16, "2" * 16}
    for n, parent in enumerate(("1" * 16, "2" * 16)):
        names = sorted(s.name for s in by_parent[parent])
        assert names == ["cryptoplane.flush", "cryptoplane.window"]
        window = next(s for s in by_parent[parent] if s.name == "cryptoplane.window")
        assert (window.start, window.end) == (10.2, 10.5)
        assert window.attrs["closed_by"] == "timer" and window.attrs["window"] == 0.3
        assert window.attrs["jobs"] == 3 and window.attrs["lanes"] == 12
        assert bool(window.attrs.get("shared")) == bool(n)
        flush = next(s for s in by_parent[parent] if s.name == "cryptoplane.flush")
        stages = by_parent[flush.span_id]
        # decode: one span per stretch in which a chunk was decoding
        decode = sorted((s.start, s.end, s.attrs["chunks"]) for s in stages
                        if s.name == "cryptoplane.decode")
        assert decode == [(10.0, 10.2, 2), (10.6, 10.7, 1)]
        device = next(s for s in stages if s.name == "cryptoplane.device")
        assert device.attrs["programs"] == "verify_rlc_dec@16"
        assert (flush.start, flush.end) == (10.0, 11.4)  # decode..device, not the window
    assert sorted(seen) == sorted(
        ["cryptoplane.window", "cryptoplane.flush", "cryptoplane.decode",
         "cryptoplane.decode", "cryptoplane.pack", "cryptoplane.device"])


def test_plane_bridge_without_a_window_or_a_context():
    """A remote brief carries no window of its own (it ran on the server),
    and submissions with no active span get one standalone trace."""
    t = tracer.Tracer()
    tracer.plane_span_bridge(t)(_flush_stats(window_span=None, parents=()))
    names = [s.name for s in t.spans]
    assert "cryptoplane.window" not in names and "cryptoplane.flush" in names
    assert len({s.trace_id for s in t.spans}) == 1
    device = next(s for s in t.spans if s.name == "cryptoplane.device")
    assert "programs" not in device.attrs
