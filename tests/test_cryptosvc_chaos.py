"""Remote crypto-plane service chaos scenarios (ISSUE 17 acceptance).

Two in-process simnet clusters share ONE crypto-plane service over real
localhost sockets — the paper's "N DV clusters, one device mesh"
topology, jax-free (SimHostPlane device). The suite drives the
failure-first contract end to end:

  1. kill-mid-flush — the server is SIGKILL'd (`abort()`: transports
     dropped without goodbye frames) while duties are in flight. Both
     clusters complete EVERY duty via local-ladder failover (zero
     missed slots), a restarted server on the same port gets automatic
     reconnects, remote serving resumes, and the
     tpu_plane_remote_failovers_total / shed / disconnect families
     attribute every event to the right tenant.
  2. socket-level misbehavior through `testutil.chaos.ChaosServiceProxy`
     — corrupt frames (typed CodecError teardown, server address never
     mutes), partition blackholes (heartbeat-miss detection), heal and
     resume.

Progress-based deadlines throughout (the chaos-suite discipline): a
loaded CI box may be slow, but each window must keep moving.
"""

import asyncio

import pytest

from charon_tpu import tbls
from charon_tpu.app.metrics import ClusterMetrics
from charon_tpu.core.cryptoplane import SlotCoalescer
from charon_tpu.core.cryptosvc import CryptoPlaneService, TenantQuota
from charon_tpu.core.cryptosvc_client import RemotePlane
from charon_tpu.core.cryptosvc_server import CryptoServiceServer
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.chaos import ChaosConfig, ChaosServiceProxy
from charon_tpu.testutil.simnet import SimHostPlane, build_cluster
from charon_tpu.testutil.waiting import wait_progress

SEED = 20260808

TOKENS = {"c1": "token-c1", "c2": "token-c2"}


@pytest.fixture(autouse=True)
def host_tbls():
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


def _atts_by_slot(beacon) -> dict[int, int]:
    out: dict[int, int] = {}
    for a in beacon.attestations:
        out[a.data.slot] = out.get(a.data.slot, 0) + 1
    return out


def _full_slots(beacon, after: int = -1) -> list[int]:
    return sorted(
        s for s, c in _atts_by_slot(beacon).items() if c >= 4 and s > after
    )


def _start(cluster):
    return [
        asyncio.create_task(node.scheduler.run())
        for node in cluster.nodes
    ]


async def _stop(cluster, tasks):
    for node in cluster.nodes:
        node.scheduler.stop()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


def _shared_service():
    """One coalescer + service shared by every dialing cluster."""
    # device_s matches the simnet default: the shared service absorbs
    # BOTH clusters' verify traffic on one core here, and a slower fake
    # device would queue past the clients' request timeout (every job
    # would fail over on "timeout" and the remote rung would never win)
    coal = SlotCoalescer(
        SimHostPlane(3, device_s=0.002), window=0.005, decode_workers=2
    )
    svc = CryptoPlaneService(coal, round_lanes=4096)
    for tenant in TOKENS:
        svc.register(tenant, TenantQuota(max_queue_lanes=4096))
    return coal, svc


def _counter_total(metric, tenant: str) -> float:
    total = 0.0
    for fam in metric.collect():
        for s in fam.samples:
            if s.name.endswith("_total") and s.labels.get("tenant") == tenant:
                total += s.value
    return total


# -- 1. kill mid-flush: failover, zero missed, reconnect, attribution --------


def test_kill_mid_flush_both_clusters_zero_missed():
    async def run():
        # 4 s slots: 8 nodes + the shared server run on ONE event loop,
        # and a slot of the two clusters costs it 1.4 CPU-s (0.70 each:
        # pure-python decode + native BLS, my sandbox, PR 41). A tier-1
        # worker under six-fold load has a third of a core — faster
        # slots oversubscribe the loop, every remote round trip turns
        # into a timeout and schedulers skip slots
        c1 = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=4.0,
            crypto_plane=True, chaos=ChaosConfig(seed=SEED),
        )
        c2 = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=4.0,
            crypto_plane=True, chaos=ChaosConfig(seed=SEED + 1),
        )
        coal, svc = _shared_service()
        server = CryptoServiceServer(svc, TOKENS, port=0)
        await server.start()
        port = server.port

        # ONE shared registry, tenant identity bound per cluster: the
        # attribution assertions below read per-tenant totals out of
        # the same families a production scrape would
        metrics = ClusterMetrics("hash", "shared-mesh", "node0")
        clients: list[RemotePlane] = []
        for tenant, cluster in (("c1", c1), ("c2", c2)):
            for node in cluster.nodes:
                rp = RemotePlane(
                    "127.0.0.1", port, tenant, TOKENS[tenant],
                    local=node.crypto_plane,
                    observer=metrics.remote_hook(tenant),
                    # generous liveness budget: 8 nodes + server share
                    # ONE event loop here, and synchronous BLS work can
                    # stall it past a tight heartbeat window. The kill
                    # below is detected by EOF (reason "io"), not the
                    # heartbeat, so detection stays immediate.
                    heartbeat_timeout=2.0,
                    request_timeout=4.0,
                )
                await rp.start()
                # the verifier is the plane consumer in simnet builds;
                # the node's own coalescer stays as the local rung
                node.parsigex.verifier.plane = rp
                clients.append(rp)
        c1_clients, c2_clients = clients[:4], clients[4:]
        server2 = None

        tasks = _start(c1) + _start(c2)
        try:
            # phase A: remote serving — both clusters complete duties
            # with every partial verified through the shared service
            await wait_progress(
                lambda: len(_full_slots(c1.beacon)) >= 2
                and len(_full_slots(c2.beacon)) >= 2
                and sum(rp.remote_jobs for rp in clients) > 0,
                probe=lambda: (
                    len(c1.beacon.attestations),
                    len(c2.beacon.attestations),
                    sum(rp.remote_jobs for rp in clients),
                ),
                what="two full slots on each cluster and a job served remotely",
            )
            assert server.served_jobs > 0

            # phase B: SIGKILL mid-flight. abort() drops every
            # connection transport with no goodbye frame while duty
            # verifies stream in — exactly a killed process.
            kill1 = max(_full_slots(c1.beacon))
            kill2 = max(_full_slots(c2.beacon))
            server.abort()

            # both clusters keep completing EVERY slot on the local
            # ladder: three more full slots each, no gaps — over the
            # slots each cluster's four schedulers ticked. (A slot the
            # starved loop never gave the schedulers is no duty the
            # failover lost; at 4 s slots a quiet box skips none.)
            def served(cluster, kill):
                return sorted(
                    set(_full_slots(cluster.beacon, after=kill))
                    & cluster.slots_given()
                )

            await wait_progress(
                lambda: len(served(c1, kill1)) >= 3
                and len(served(c2, kill2)) >= 3,
                probe=lambda: (
                    len(c1.beacon.attestations),
                    len(c2.beacon.attestations),
                ),
                what="three full slots on each cluster after the kill",
            )
            for cluster, kill in ((c1, kill1), (c2, kill2)):
                completed, given = served(cluster, kill), cluster.slots_given()
                missed = [
                    s
                    for s in range(kill + 1, max(completed))
                    if s in given and s not in completed
                ]
                assert missed == [], f"missed slots across the kill: {missed}"

            # every client degraded (typed reasons, no crashes) and the
            # metric families attribute per tenant: each cluster's
            # failovers land ONLY under its own tenant label. Events
            # keep flowing while we read, so bracket the family total
            # between two client-counter snapshots instead of demanding
            # an instantaneous equality.
            for rps, tenant in ((c1_clients, "c1"), (c2_clients, "c2")):
                before_snap = sum(
                    sum(rp.failovers.values()) for rp in rps
                )
                fam_total = _counter_total(
                    metrics.plane_remote_failovers, tenant
                )
                after_snap = sum(
                    sum(rp.failovers.values()) for rp in rps
                )
                assert before_snap > 0
                assert before_snap <= fam_total <= after_snap
                d_before = sum(
                    sum(rp.disconnects.values()) for rp in rps
                )
                d_fam = _counter_total(
                    metrics.plane_remote_disconnects, tenant
                )
                d_after = sum(
                    sum(rp.disconnects.values()) for rp in rps
                )
                assert d_before <= d_fam <= d_after

            # phase C: restart on the SAME port — supervisors reconnect
            # on their backoff schedule and remote serving resumes
            server2 = CryptoServiceServer(svc, TOKENS, port=port)
            await server2.start()
            before = sum(rp.remote_jobs for rp in clients)
            await wait_progress(
                lambda: all(rp.connects >= 2 for rp in clients)
                and sum(rp.remote_jobs for rp in clients) > before,
                probe=lambda: (
                    tuple(rp.connects for rp in clients),
                    sum(rp.remote_jobs for rp in clients),
                ),
                what="every client reconnected to the restarted server and a job served remotely",
            )
            assert all(rp.reconnect_delays for rp in clients)
        finally:
            await _stop(c1, tasks[:4])
            await _stop(c2, tasks[4:])
            for rp in clients:
                await rp.close()
            if server2 is not None:
                await server2.close()
            svc.close()
            coal.close()
            c1.close()
            c2.close()

    asyncio.run(run())


# -- 1b. post-mortem: the flight recorder names the fault (ISSUE 19) ---------


def test_kill_mid_flush_postmortem_names_fault(tmp_path):
    """ISSUE 19 acceptance: kill the shared crypto-plane server while
    two tenants are verifying through it, dump each tenant node's
    flight recorder, and assert the MERGED timeline names (a) the
    aborted server endpoint, (b) the typed failover reason, and (c)
    every affected tenant — the post-mortem an operator reads after a
    real incident, reconstructed purely from the per-node dumps."""
    from charon_tpu.app import flightrec

    async def run():
        impl = tbls.get_implementation()
        sk = impl.generate_secret_key()
        pk = impl.secret_to_public_key(sk)
        items = [
            (pk, bytes([i]) * 32, impl.sign(sk, bytes([i]) * 32))
            for i in range(4)
        ]

        coal, svc = _shared_service()
        server = CryptoServiceServer(svc, TOKENS, port=0)
        await server.start()
        addr = f"127.0.0.1:{server.port}"

        locals_, clients, recs = [], [], {}
        for tenant in ("c1", "c2"):
            rec = flightrec.FlightRecorder(node=f"{tenant}-node0")
            recs[tenant] = rec
            local = SlotCoalescer(
                SimHostPlane(3), window=0.005, decode_workers=2
            )
            locals_.append(local)
            client = RemotePlane(
                "127.0.0.1", server.port, tenant, TOKENS[tenant],
                local=local,
                observer=flightrec.remote_hook(rec, tenant, addr=addr),
                heartbeat_timeout=2.0, request_timeout=4.0,
            )
            await client.start()
            clients.append(client)
        try:
            # phase A: remote serving, recorded as connect events
            await wait_progress(
                lambda: all(c.state != "down" for c in clients),
                probe=lambda: tuple(c.connects for c in clients),
                what="both clients connected",
            )
            for client in clients:
                assert await client.verify(list(items)) == [True] * 4

            # phase B: SIGKILL mid-flight; every next round trip fails
            # over down the local ladder with a typed reason
            server.abort()
            for client in clients:
                assert await client.verify(list(items)) == [True] * 4
            await wait_progress(
                lambda: all(
                    sum(c.failovers.values()) > 0 for c in clients
                ),
                probe=lambda: tuple(
                    sum(c.failovers.values()) for c in clients
                ),
                what="a failover on each client",
            )

            # phase C: each node dumps its OWN ring; the incident is
            # reconstructed only from the merged JSONL
            paths = []
            for tenant, rec in recs.items():
                path = str(tmp_path / f"{tenant}.flight.jsonl")
                assert rec.dump_jsonl(path, trigger="demand") > 0
                paths.append(path)
            merged = flightrec.merge_jsonl(paths)
            timeline = flightrec.render_timeline(merged)

            # (a) the aborted server endpoint is named
            assert addr in timeline
            # (b) the failover carries its typed reason
            failovers = [e for e in merged if e["kind"] == "failover"]
            assert failovers
            reasons = {e["fields"].get("reason") for e in failovers}
            assert reasons <= {"down", "io", "timeout", "heartbeat"}
            disconnects = [e for e in merged if e["kind"] == "disconnect"]
            assert disconnects
            # (c) every affected tenant appears, attributed to its node
            assert {e["tenant"] for e in failovers} == {"c1", "c2"}
            assert {e["node"] for e in merged} == {"c1-node0", "c2-node0"}
            # wall-clock merge puts the connect epoch before the fault
            kinds_in_order = [e["kind"] for e in merged]
            assert kinds_in_order.index("connect") < kinds_in_order.index(
                "failover"
            )
            for needle in ("failover", "c1", "c2", "reason="):
                assert needle in timeline, needle
        finally:
            for client in clients:
                await client.close()
            svc.close()
            coal.close()
            for local in locals_:
                local.close()

    asyncio.run(run())


# -- 2. socket-level misbehavior through the chaos proxy ---------------------


def test_proxy_corruption_then_partition_then_heal():
    """Corrupt frames must surface as typed codec teardowns (server
    address exempt from mutes), a partition must be caught by the
    heartbeat (monotonic) within its timeout, and healing must bring
    remote serving back — all while every submitted job completes."""

    async def run():
        impl = tbls.get_implementation()
        sk = impl.generate_secret_key()
        pk = impl.secret_to_public_key(sk)
        items = [
            (pk, bytes([i]) * 32, impl.sign(sk, bytes([i]) * 32))
            for i in range(4)
        ]

        coal, svc = _shared_service()
        server = CryptoServiceServer(svc, TOKENS, port=0)
        await server.start()
        proxy = ChaosServiceProxy(
            "127.0.0.1", server.port, ChaosConfig(seed=SEED)
        )
        await proxy.start()

        local = SlotCoalescer(
            SimHostPlane(3), window=0.005, decode_workers=2
        )
        client = RemotePlane(
            "127.0.0.1", proxy.port, "c1", TOKENS["c1"],
            local=local, heartbeat_timeout=0.4, request_timeout=2.0,
        )
        await client.start()

        async def served_remotely_after(before):
            while client.remote_jobs == before:
                assert await client.verify(list(items)) == [True] * 4
                await asyncio.sleep(0.05)

        try:
            # clean path through the proxy: probe -> up, remote serving
            await wait_progress(
                lambda: client.state != "down",
                probe=lambda: client.connects,
                what="the client connected through the proxy",
            )
            assert await client.verify(list(items)) == [True] * 4
            assert client.remote_jobs == 1

            # phase: corruption — every chunk mangled; the next round
            # trip dies as a typed codec/io teardown and fails over
            proxy.corrupt = 1.0
            res = await client.verify(list(items))
            assert res == [True] * 4  # local rung won the duty
            assert client.local_jobs >= 1
            assert proxy.corrupted > 0
            # the pinned server address NEVER escalates into a mute
            assert not client.quarantine.muted(client.addr)

            # heal the corruption: reconnect restores remote serving
            proxy.corrupt = 0.0
            before = client.remote_jobs
            await wait_progress(
                lambda: client.state != "down",
                probe=lambda: client.connects,
                what="the client connected through the proxy",
            )
            await asyncio.wait_for(served_remotely_after(before), 30)
            assert client.remote_jobs > before

            # phase: partition — bytes vanish silently; only the
            # monotonic heartbeat can notice, within its timeout
            proxy.partition()
            await wait_progress(
                lambda: client.state == "down",
                probe=lambda: client.disconnects.copy(),
                what="the heartbeat to notice the partition",
                first_window=30.0,
            )
            assert (
                client.disconnects.get("heartbeat", 0)
                + client.disconnects.get("timeout", 0)
                + client.disconnects.get("io", 0)
                > 0
            )
            # during the outage jobs still complete, attributed "down"
            assert await client.verify(list(items)) == [True] * 4
            assert client.failovers.get("down", 0) >= 1

            # heal: dials pass again, serving resumes
            proxy.heal()
            before = client.remote_jobs
            await wait_progress(
                lambda: client.state != "down",
                probe=lambda: client.connects,
                what="the client connected through the proxy",
            )
            await asyncio.wait_for(served_remotely_after(before), 30)
        finally:
            await client.close()
            await proxy.close()
            await server.close()
            svc.close()
            coal.close()
            local.close()

    asyncio.run(run())
