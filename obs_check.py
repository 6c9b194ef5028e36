#!/usr/bin/env python
"""obs_check.py — observability tier gate (ISSUE 4 acceptance).

Boots an in-process 4-node simnet with per-node tracers + the
SimHostPlane crypto coalescer, serves the monitoring endpoint off node
1's tracer, completes at least --duties attestation duties, then
scrapes and asserts:

  * /metrics          — per-step latency histograms + duty-wall series
                        present, slow-duty counter family registered;
  * /debug/traces     — non-empty span export;
  * /debug/duty/<slot> — well-formed JSON timeline (plus the text
                        waterfall) for a completed duty, 404 for an
                        unknown slot;
  * per-node JSONL exports merge into ONE duty-rooted trace per duty
    covering every wire edge plus cryptoplane decode/device stages;
  * /debug/flight    — the flight-recorder ring over HTTP (JSON + text
                        timeline) and the core_slo_* burn-rate gauges
                        on /metrics (ISSUE 19), then every node's
                        flight dump merged into one wall-clock-ordered
                        cross-node incident record.

jax-free and CPU-safe (the device program is a wall-clock sleep), so
it runs in the fast tier tail; exit 1 on any violated gate.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import tempfile
import urllib.error
import urllib.request

WIRE_EDGES = [
    "fetcher.fetch",
    "consensus.propose",
    "dutydb.store",
    "parsigdb.store_internal",
    "parsigex.broadcast",
    "parsigdb.store_external",
    "sigagg.aggregate",
    "aggsigdb.store",
    "broadcaster.broadcast",
]


def _completed_attester_slots(beacon, n: int) -> list[int]:
    by_slot: dict[int, int] = {}
    for a in beacon.attestations:
        by_slot[a.data.slot] = by_slot.get(a.data.slot, 0) + 1
    return sorted(s for s, c in by_slot.items() if c >= n)


def _get(url: str) -> tuple[int, bytes]:
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.read()


async def main(args) -> int:
    from charon_tpu import tbls
    from charon_tpu.app import flightrec, tracer
    from charon_tpu.app.health import SLOEngine
    from charon_tpu.app.metrics import (
        ClusterMetrics,
        serve_monitoring,
        span_metrics,
    )
    from charon_tpu.core.types import Duty, DutyType
    from charon_tpu.testutil.simnet import build_cluster

    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        from charon_tpu.tbls.python_impl import PythonImpl

        tbls.set_implementation(PythonImpl())

    failures: list[str] = []

    def gate(ok: bool, what: str) -> None:
        print(("PASS " if ok else "FAIL ") + what)
        if not ok:
            failures.append(what)

    with tempfile.TemporaryDirectory(prefix="obs-traces-") as trace_dir:
        cluster = build_cluster(
            n=4,
            t=3,
            slot_duration=args.slot_duration,
            tracing_on=True,
            trace_dir=trace_dir,
            crypto_plane=True,
            flightrec=True,
        )
        # monitoring endpoint off node 1's tracer + a metrics registry
        # fed by its span ends — the same wiring app/run.py does
        metrics = ClusterMetrics("0xobs", "obs-check", "node0")
        node1 = cluster.nodes[0]
        node1.tracer.hooks.append(span_metrics(metrics))
        # duty SLO engine fed from node 1's tracker reports (ISSUE 19),
        # min_events=1 so a short run still produces rows
        slo = SLOEngine(min_events=1, on_alert=metrics.slo_alert_hook())
        node1.tracker.subscribe(
            lambda rep: slo.observe_duty(rep.success, tenant="obs")
        )
        server = await serve_monitoring(
            "127.0.0.1", 0, metrics, tracer=node1.tracer,
            flightrec=node1.flightrec,
        )
        port = server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"

        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:

            async def enough():
                while (
                    len(_completed_attester_slots(cluster.beacon, 4))
                    < args.duties
                ):
                    await asyncio.sleep(0.05)

            await asyncio.wait_for(enough(), timeout=90)
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)
            await asyncio.sleep(0.2)  # settle in-flight plane flushes

        slots = _completed_attester_slots(cluster.beacon, 4)[: args.duties]
        gate(len(slots) >= args.duties, f"{args.duties} duties completed")

        # drive duty expiry (production's Deadliner job): the tracker
        # only emits per-duty reports at expiry, and those reports feed
        # the flight recorder's duty ring and the SLO engine (ISSUE 19)
        for slot in slots:
            duty = Duty(slot=slot, type=DutyType.ATTESTER)
            for node in cluster.nodes:
                await node.tracker.duty_expired(duty)

        # /metrics
        status, body = await asyncio.to_thread(_get, f"{base}/metrics")
        text = body.decode()
        gate(status == 200, "/metrics responds")
        gate(
            "core_step_latency_seconds" in text
            and 'step="fetcher.fetch"' in text,
            "/metrics carries per-step latency histograms",
        )
        gate(
            "core_duty_slow_total" in text or "core_duty_wall" in text
            or "core_step_latency" in text,
            "/metrics slow-duty/latency families registered",
        )

        # /debug/traces
        status, body = await asyncio.to_thread(_get, f"{base}/debug/traces")
        spans = json.loads(body)
        gate(status == 200 and len(spans) > 0, "/debug/traces non-empty")

        # /debug/duty/<slot>
        slot = slots[0]
        status, body = await asyncio.to_thread(
            _get, f"{base}/debug/duty/{slot}"
        )
        timelines = json.loads(body)
        duty = Duty(slot=slot, type=DutyType.ATTESTER)
        tid = tracer.duty_trace_id(duty)
        gate(
            status == 200
            and any(tl["trace_id"] == tid for tl in timelines),
            f"/debug/duty/{slot} returns the duty timeline",
        )
        status, body = await asyncio.to_thread(
            _get, f"{base}/debug/duty/{slot}?format=text"
        )
        gate(
            status == 200 and b"fetcher.fetch" in body,
            f"/debug/duty/{slot}?format=text renders the waterfall",
        )
        # the served path's own spans (simnet: echo consensus and a bare
        # coalescer, so no qbft.instance and no tenant queue)
        own = (b"vapi.submit", b"parsigex.verify", b"cryptoplane.window")
        gate(
            all(name in body for name in own),
            f"/debug/duty/{slot} shows the entry and window spans",
        )
        try:
            await asyncio.to_thread(_get, f"{base}/debug/duty/999999")
            gate(False, "/debug/duty/<unknown> 404s")
        except urllib.error.HTTPError as e:
            gate(e.code == 404, "/debug/duty/<unknown> 404s")

        # core_slo_* families (ISSUE 19): evaluate the duty-miss budget
        # over the completed run and scrape the exported gauges
        metrics.observe_slo(slo.evaluate())
        status, body = await asyncio.to_thread(_get, f"{base}/metrics")
        text = body.decode()
        gate(
            "core_slo_burn_rate" in text and 'slo="duty_miss"' in text,
            "/metrics carries core_slo_burn_rate{slo=duty_miss}",
        )
        gate(
            "core_slo_budget_remaining" in text,
            "/metrics carries core_slo_budget_remaining",
        )
        gate(
            not slo.firing("duty_miss"),
            "duty-miss SLO not burning after a clean run",
        )

        # /debug/flight (ISSUE 19): node 1's ring over HTTP
        status, body = await asyncio.to_thread(_get, f"{base}/debug/flight")
        doc = json.loads(body)
        gate(
            status == 200
            and doc["schema"] == flightrec.SCHEMA_VERSION
            and len(doc["events"]) > 0,
            "/debug/flight serves the node's event ring",
        )
        categories = {e["category"] for e in doc["events"]}
        gate(
            {"flush", "duty"} <= categories,
            f"/debug/flight covers flush+duty categories (got {sorted(categories)})",
        )
        status, body = await asyncio.to_thread(
            _get, f"{base}/debug/flight?format=text"
        )
        gate(
            status == 200 and b"duty_ok" in body,
            "/debug/flight?format=text renders the incident timeline",
        )

        server.close()
        await server.wait_closed()
        cluster.close()

        # cross-node flight-recorder merge (ISSUE 19): every node dumps
        # its own ring; the merged timeline is ONE wall-clock-ordered
        # incident record covering all four nodes
        dumps = cluster.dump_flight(trace_dir)
        gate(len(dumps) == 4, "all 4 nodes dumped flight JSONL")
        fmerged = flightrec.merge_jsonl(dumps)
        gate(
            {e["node"] for e in fmerged}
            == {f"node{n.share_idx}" for n in cluster.nodes},
            "flight merge covers all 4 nodes",
        )
        walls = [e["t_wall"] for e in fmerged]
        gate(
            walls == sorted(walls),
            "flight merge is wall-clock ordered",
        )
        slot0 = slots[0]
        duty_nodes = {
            e["node"]
            for e in fmerged
            if e["category"] == "duty" and e["slot"] == slot0
        }
        gate(
            len(duty_nodes) == 4,
            f"slot {slot0}: duty outcome recorded on every node",
        )

        # cross-node JSONL merge: one trace per duty, every wire edge
        # + cryptoplane stages, no orphan parentage
        merged = tracer.merge_jsonl(cluster.trace_paths())
        gate(len(merged) > 0, "per-node JSONL span export non-empty")
        for slot in slots:
            duty = Duty(slot=slot, type=DutyType.ATTESTER)
            tid = tracer.duty_trace_id(duty)
            duty_spans = [
                s for s in merged if s["attrs"].get("duty") == str(duty)
            ]
            gate(
                bool(duty_spans)
                and {s["trace_id"] for s in duty_spans} == {tid},
                f"slot {slot}: one merged cross-node trace",
            )
            trace = [s for s in merged if s["trace_id"] == tid]
            names = {s["name"] for s in trace}
            missing = [e for e in WIRE_EDGES if e not in names]
            gate(not missing, f"slot {slot}: all wire edges spanned")
            gate(
                "cryptoplane.device" in names
                and "cryptoplane.decode" in names,
                f"slot {slot}: cryptoplane stages bridged",
            )
            ids = {s["span_id"] for s in trace}
            orphans = [
                s["name"]
                for s in trace
                if s["parent_id"] and s["parent_id"] not in ids
            ]
            gate(not orphans, f"slot {slot}: no orphan spans")

    if failures:
        print(f"\nobs gate FAILED: {len(failures)} violation(s)")
        return 1
    print("\nobs gate PASS")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--duties",
        type=int,
        default=2,
        help="attestation duties to complete before scraping",
    )
    ap.add_argument("--slot-duration", type=float, default=0.2)
    ap.add_argument(
        "--fast",
        action="store_true",
        help="fast-tier subset: a single duty",
    )
    args = ap.parse_args()
    if args.fast:
        args.duties = 1
    raise SystemExit(asyncio.run(main(args)))
