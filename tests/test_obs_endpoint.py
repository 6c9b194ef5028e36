"""Observability surfaces of the duty-rooted tracing plane (ISSUE 4):
the /debug/duty/<slot> timeline endpoint, trace ids stamped into log
records, per-step latency histograms and the slow-duty detector.
"""

from __future__ import annotations

import asyncio
import json
import urllib.error
import urllib.request

import pytest

from charon_tpu.app import log, tracer
from charon_tpu.app.metrics import (
    ClusterMetrics,
    SlowDutyDetector,
    serve_monitoring,
    span_metrics,
)
from charon_tpu.core.types import Duty, DutyType


def _record_duty(t: tracer.Tracer, duty: Duty) -> None:
    with tracer.span("fetcher.fetch", duty=duty, tracer=t):
        with tracer.span("consensus.propose", tracer=t):
            pass
        with tracer.span("dutydb.store", tracer=t):
            pass


def test_debug_duty_endpoint_timeline_and_404():
    async def run():
        t = tracer.Tracer()
        duty = Duty(slot=17, type=DutyType.ATTESTER)
        _record_duty(t, duty)
        metrics = ClusterMetrics("0xdead", "test", "node0")
        server = await serve_monitoring("127.0.0.1", 0, metrics, tracer=t)
        port = server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"

        def get(url):
            with urllib.request.urlopen(url) as resp:
                return resp.status, resp.read()

        status, body = await asyncio.to_thread(get, f"{base}/debug/duty/17")
        assert status == 200
        (timeline,) = json.loads(body)
        assert timeline["trace_id"] == tracer.duty_trace_id(duty)
        assert timeline["duty"] == str(duty)
        assert timeline["wall_us"] >= 0
        names = [s["name"] for s in timeline["spans"]]
        assert names[0] == "fetcher.fetch"
        assert set(names) == {
            "fetcher.fetch",
            "consensus.propose",
            "dutydb.store",
        }
        # nesting is depth-annotated in span order
        depths = {s["name"]: s["depth"] for s in timeline["spans"]}
        assert depths["fetcher.fetch"] == 0
        assert depths["consensus.propose"] == 1

        # plain-text waterfall
        status, body = await asyncio.to_thread(
            get, f"{base}/debug/duty/17?format=text"
        )
        assert status == 200
        text = body.decode()
        assert "fetcher.fetch" in text and "wall" in text and "#" in text

        # unknown slot and malformed slot both 404
        for bad in ("/debug/duty/999", "/debug/duty/notaslot"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                await asyncio.to_thread(get, base + bad)
            assert exc.value.code == 404

        server.close()
        await server.wait_closed()

    asyncio.run(run())


def test_debug_flight_endpoint_filters_views_and_404():
    from charon_tpu.app import flightrec
    from charon_tpu.app.planeprof import PlaneProfiler

    async def run():
        rec = flightrec.FlightRecorder(node="node0")
        rec.record("tenant", "shed", tenant="tenant-a", slot=9, reason="queue")
        rec.record("remote", "failover", tenant="tenant-a", reason="io")
        rec.record("duty", "duty_ok", tenant="tenant-b", slot=10)
        prof = PlaneProfiler()
        prof.program_hook()("mesh/verify", 0.004, 64)
        metrics = ClusterMetrics("0xdead", "test", "node0")
        server = await serve_monitoring(
            "127.0.0.1", 0, metrics, flightrec=rec, profiler=prof
        )
        port = server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"

        def get(url):
            with urllib.request.urlopen(url) as resp:
                return resp.status, resp.read()

        status, body = await asyncio.to_thread(get, f"{base}/debug/flight")
        assert status == 200
        doc = json.loads(body)
        assert doc["schema"] == flightrec.SCHEMA_VERSION
        assert doc["node"] == "node0"
        assert [e["kind"] for e in doc["events"]] == [
            "shed",
            "failover",
            "duty_ok",
        ]

        # filters: category, tenant, slot, limit
        for query, kinds in (
            ("category=remote", ["failover"]),
            ("tenant=tenant-a", ["shed", "failover"]),
            ("slot=9", ["shed"]),
            ("limit=1", ["duty_ok"]),
        ):
            _, body = await asyncio.to_thread(
                get, f"{base}/debug/flight?{query}"
            )
            got = [e["kind"] for e in json.loads(body)["events"]]
            assert got == kinds, query

        # plain-text incident timeline
        status, body = await asyncio.to_thread(
            get, f"{base}/debug/flight?format=text"
        )
        assert status == 200
        text = body.decode()
        assert "failover" in text and "tenant=tenant-a" in text

        # profiler view
        status, body = await asyncio.to_thread(
            get, f"{base}/debug/flight?view=profile"
        )
        assert status == 200
        snap = json.loads(body)
        assert snap["pending_samples"] == 1

        server.close()
        await server.wait_closed()

        # no recorder wired -> 404, never a fake empty incident
        bare = await serve_monitoring("127.0.0.1", 0, metrics)
        bare_port = bare.sockets[0].getsockname()[1]
        with pytest.raises(urllib.error.HTTPError) as exc:
            await asyncio.to_thread(
                get, f"http://127.0.0.1:{bare_port}/debug/flight"
            )
        assert exc.value.code == 404
        bare.close()
        await bare.wait_closed()

    asyncio.run(run())


def test_log_records_carry_trace_id(caplog):
    import logging

    duty = Duty(slot=4, type=DutyType.PROPOSER)
    t = tracer.Tracer()
    with caplog.at_level(logging.INFO, logger="charon_tpu"):
        with tracer.span("fetcher.fetch", duty=duty, tracer=t):
            log.info("inside span", topic="test")
        log.info("outside span", topic="test")
        with tracer.span("fetcher.fetch", duty=duty, tracer=t):
            log.info("explicit", topic="test", trace_id="mine")
    inside, outside, explicit = [r.getMessage() for r in caplog.records][-3:]
    assert f"trace_id={tracer.duty_trace_id(duty)}" in inside
    assert "trace_id" not in outside
    # explicit call-site field wins over the ambient span
    assert "trace_id=mine" in explicit


def test_span_metrics_step_latency_histogram():
    metrics = ClusterMetrics("0xdead", "test", "node0")
    t = tracer.Tracer()
    t.hooks.append(span_metrics(metrics))
    duty = Duty(slot=2, type=DutyType.ATTESTER)
    _record_duty(t, duty)
    rendered = metrics.render().decode()
    assert (
        'core_step_latency_seconds_count{cluster_hash="0xdead",'
        in rendered
    )
    for step in ("fetcher.fetch", "consensus.propose", "dutydb.store"):
        assert f'step="{step}"' in rendered


def test_slow_duty_detector():
    metrics = ClusterMetrics("0xdead", "test", "node0")
    det = SlowDutyDetector(metrics)
    t = tracer.Tracer()
    t.hooks.append(det.observe)
    duty = Duty(slot=30, type=DutyType.ATTESTER)
    _record_duty(t, duty)

    # generous budget: not slow
    wall = det.finalize(duty, budget=60.0)
    assert wall is not None and wall >= 0
    assert det.slow_total == 0
    # state popped: a second finalize sees no spans
    assert det.finalize(duty, budget=60.0) is None

    # sub-zero budget trip: re-record and finalize with a tiny budget
    _record_duty(t, duty)
    wall = det.finalize(duty, budget=1e-9)
    assert wall is not None
    assert det.slow_total == 1
    assert det.last["slow"] is True
    rendered = metrics.render().decode()
    assert "core_duty_slow_total" in rendered
    assert "core_duty_wall_seconds" in rendered
    # duties with no spans at all never flag
    assert det.finalize(Duty(slot=31, type=DutyType.ATTESTER), 1e-9) is None
    assert det.slow_total == 1


def test_a_built_node_serves_and_keeps_its_own_tracer(tmp_path, monkeypatch):
    """build_node gives the node a tracer of its own: /debug/traces and
    /debug/duty/<slot> serve that ring, a bare component beside it in the
    process records into the process-global one (so neither the node's
    ring nor the hooks on it see its spans), and the registry still finds
    the node's tracer after its lifecycle has stopped."""
    import socket

    from charon_tpu.app.run import TRACE_RING_SPANS, Config, build_node
    from charon_tpu.cmd.cli import main as cli
    from charon_tpu.core.parsigex import MemTransport, ParSigEx

    monkeypatch.setattr(tracer, "_NODE_TRACERS", {})
    out = tmp_path / "c"
    cli(["create-cluster", "--nodes", "2", "--threshold", "2", "--validators", "1",
         "--output-dir", str(out)])
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    async def run():
        node = await build_node(Config(
            data_dir=str(out / "node0"), node_index=0, simnet=True, slot_duration=0.5,
            slots_per_epoch=8, use_tpu_tbls=False, monitoring_port=port))
        assert node.tracer is not tracer.global_tracer()
        assert tracer.node_tracers() == {0: node.tracer}
        assert node.tracer.spans.maxlen == TRACE_RING_SPANS
        hooked = []
        node.tracer.hooks.append(lambda s: hooked.append(s.name))
        stop = asyncio.Event()
        life = asyncio.create_task(node.life.run(stop))
        duty = Duty(slot=4, type=DutyType.ATTESTER)
        # a peer's component, built bare in the node's process
        before = len(tracer.global_tracer().spans)
        await ParSigEx(2, MemTransport()).receive(duty, {})
        assert len(tracer.global_tracer().spans) == before + 1
        with tracer.span("vapi.submit", duty=duty, tracer=node.tracer):
            pass

        def get(path):
            with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as resp:
                return json.loads(resp.read())

        async def served(path):
            while True:  # until the endpoint listens
                try:
                    return await asyncio.to_thread(get, path)
                except OSError:
                    await asyncio.sleep(0.05)

        traces = await asyncio.wait_for(served("/debug/traces"), 30)
        names = {s["name"] for s in traces}
        assert "vapi.submit" in names and "parsigex.receive" not in names
        assert "parsigex.receive" not in hooked and "vapi.submit" in hooked
        (timeline,) = [tl for tl in await asyncio.to_thread(get, "/debug/duty/4")
                       if tl["duty"] == str(duty)]
        assert [s["name"] for s in timeline["spans"]] == ["vapi.submit"]
        stop.set()
        await asyncio.wait_for(life, 30)
        return node

    node = asyncio.run(run())
    # torn down; the ring and its registry entry stay for a reader
    assert tracer.node_tracers()[0] is node.tracer
    assert any(s.name == "vapi.submit" for s in node.tracer.spans)
    assert node.tracer.evicted == 0
