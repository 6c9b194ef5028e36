"""App infrastructure: lifecycle ordering, retry, featureset, health,
metrics endpoint, tracker failure analysis."""

import asyncio

import pytest

from charon_tpu.app import featureset
from charon_tpu.app.health import Check, HealthChecker, MetricStore
from charon_tpu.app.lifecycle import LifecycleManager, Order
from charon_tpu.app.metrics import ClusterMetrics, serve_monitoring
from charon_tpu.app.retry import Retryer
from charon_tpu.core.tracker import Reason, Step, Tracker, tracking
from charon_tpu.core.types import Duty, DutyType
from charon_tpu.testutil.waiting import wait_until


def test_lifecycle_order_and_shutdown():
    async def run():
        events = []
        life = LifecycleManager()

        async def bg(name):
            events.append(f"start:{name}")
            try:
                await asyncio.sleep(100)
            except asyncio.CancelledError:
                raise

        life.register_start(Order.SCHEDULER, "sched", lambda: bg("sched"))
        life.register_start(Order.P2P, "p2p", lambda: bg("p2p"))

        async def stop_hook():
            events.append("stop:p2p")

        life.register_stop(Order.P2P, "p2p", stop_hook)

        stop = asyncio.Event()
        task = asyncio.create_task(life.run(stop))
        await wait_until(
                lambda: len(events) >= 2,
                "both start hooks",
            )
        assert events == ["start:p2p", "start:sched"]  # ordered
        stop.set()
        await asyncio.wait_for(task, 10)
        assert events[-1] == "stop:p2p"

    asyncio.run(run())


def test_retryer_retries_until_deadline():
    async def run():
        now = [0.0]
        attempts = []

        async def flaky(duty):
            attempts.append(now[0])
            now[0] += 1.1  # each attempt costs 1.1s virtual time
            raise ConnectionError("bn down")

        r = Retryer(
            deadline_of=lambda duty: 3.0,
            now=lambda: now[0],
            backoff=0.0,  # no real sleeping in tests
        )
        await r.retry("fetch", Duty(1, DutyType.ATTESTER), flaky)
        assert 2 <= len(attempts) <= 4  # bounded by the deadline

        async def boom(duty):
            raise ValueError("programming error")

        # fresh duty window (the clock ran past the previous deadline,
        # and an expired duty never even starts — Deadliner semantics)
        now[0] = 0.0
        with pytest.raises(ValueError):
            await r.retry("fetch", Duty(1, DutyType.ATTESTER), boom)

    asyncio.run(run())


def test_featureset_statuses():
    featureset.init(featureset.Status.STABLE)
    assert featureset.enabled(featureset.Feature.QBFT_CONSENSUS)
    assert not featureset.enabled(featureset.Feature.AGG_SIG_DB_V2)
    featureset.init(
        featureset.Status.STABLE, enable=[featureset.Feature.AGG_SIG_DB_V2]
    )
    assert featureset.enabled(featureset.Feature.AGG_SIG_DB_V2)
    featureset.init(
        featureset.Status.STABLE, disable=[featureset.Feature.QBFT_CONSENSUS]
    )
    assert not featureset.enabled(featureset.Feature.QBFT_CONSENSUS)
    featureset.init(featureset.Status.STABLE)


def test_health_checks():
    from charon_tpu.app.health import SEVERITY_CRITICAL

    now = [0.0]
    store = MetricStore(now=lambda: now[0])
    checker = HealthChecker(
        store,
        [
            Check(
                "errors",
                "err spike",
                lambda m, md: m.increase("errs") > 10,
                SEVERITY_CRITICAL,
            ),
            Check(
                "peers",
                "low peers",
                lambda m, md: m.latest("peers", 0) < 2,
                SEVERITY_CRITICAL,
            ),
        ],
    )
    store.sample("errs", 0)
    store.sample("peers", 3)
    assert checker.healthy()
    now[0] = 60
    store.sample("errs", 20)  # +20 errors in window
    assert checker.evaluate() == {"errors": True, "peers": False}
    assert not checker.healthy()


def test_health_catalogue_and_severities():
    """The reference catalogue (ref: health/checks.go:41-151): scaled
    log-rate thresholds, critical-vs-warning readiness semantics, clock
    skew from peerinfo."""
    from charon_tpu.app.health import Metadata, default_checks

    now = [0.0]
    store = MetricStore(now=lambda: now[0])
    checker = HealthChecker(store, metadata=Metadata(num_validators=2, quorum=3))
    assert {c.name for c in checker.checks} == {
        "high_error_log_rate",
        "high_warning_log_rate",
        "beacon_node_syncing",
        "insufficient_connected_peers",
        "proposal_failures",
        "failed_duties",
        "high_registration_failures_rate",
        "high_clock_skew",
        "pending_validators",
    }
    # seed a healthy baseline
    store.sample("app_log_errors", 0)
    store.sample("app_log_warnings", 0)
    store.sample("app_beacon_syncing", 0)
    store.sample("p2p_peers_connected", 3)
    store.sample("core_tracker_failed_duties", 0)
    store.sample("core_tracker_failed_proposals", 0)
    store.sample("core_bcast_recast_errors", 0)
    store.sample("app_peerinfo_clock_offset_abs", 0.1)
    assert checker.healthy()
    assert not checker.failing()

    # 2 validators allow 4 errors per window; 5 trips the warning but
    # NOT readiness (severity=warning)
    now[0] = 60
    store.sample("app_log_errors", 5)
    assert checker.evaluate()["high_error_log_rate"]
    assert checker.healthy()

    # a transient peer dip does NOT trip the check: gaugeMax over the
    # window still sees the healthy count (ref: checker.go gaugeMax)
    store.sample("p2p_peers_connected", 1)
    assert checker.healthy()
    # a SUSTAINED loss does: once healthy samples age out of the window,
    # the max drops below quorum-1 and readiness flips (critical)
    now[0] = 700
    store.sample("p2p_peers_connected", 1)
    assert checker.evaluate()["insufficient_connected_peers"]
    assert not checker.healthy()
    store.sample("p2p_peers_connected", 3)
    assert checker.healthy()

    # clock skew beyond 2s warns
    store.sample("app_peerinfo_clock_offset_abs", 3.5)
    assert checker.evaluate()["high_clock_skew"]
    assert checker.healthy()  # warning severity


def test_metrics_endpoint():
    async def run():
        m = ClusterMetrics("0xhash", "c", "node0")
        m.labels(m.bcast_total, "attester").inc()
        server = await serve_monitoring("127.0.0.1", 0, m)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
        await writer.drain()
        data = await reader.read(-1)
        assert b"core_bcast_broadcast_total" in data
        assert b'peer="node0"' in data
        writer.close()
        server.close()

    asyncio.run(run())


def test_tracker_failure_analysis():
    async def run():
        duty = Duty(3, DutyType.ATTESTER)
        tr = Tracker(peer_share_indices=[1, 2, 3, 4])
        reports = []
        tr.subscribe(reports.append)

        # simulate a duty that got through consensus but no partials
        for s in (Step.SCHEDULER, Step.FETCHER, Step.CONSENSUS, Step.DUTY_DB):
            tr.step_event(duty, s)
        tr.partial_observed(duty, 1)
        report = await tr.duty_expired(duty)
        assert not report.success
        assert report.failed_step == Step.VALIDATOR_API
        assert report.reason == Reason.NO_LOCAL_PARTIAL
        assert report.participation == {1: True, 2: False, 3: False, 4: False}
        assert reports == [report]

        # successful duty
        duty2 = Duty(4, DutyType.ATTESTER)
        for s in Step:
            tr.step_event(duty2, s)
        report2 = await tr.duty_expired(duty2)
        assert report2.success and report2.failed_step is None

    asyncio.run(run())


def test_tracking_wire_option():
    async def run():
        duty = Duty(5, DutyType.ATTESTER)
        tr = Tracker(peer_share_indices=[1, 2])

        async def fetch(duty, defs):
            return None

        wrapped = tracking(tr)("fetcher.fetch", fetch)
        await wrapped(duty, {})
        assert Step.SCHEDULER in tr._steps[duty]
        assert Step.FETCHER in tr._steps[duty]

        async def broken(duty, defs):
            raise RuntimeError("bn error")

        wrapped_bad = tracking(tr)("consensus.propose", broken)
        with pytest.raises(RuntimeError):
            await wrapped_bad(duty, {})
        assert tr._errors[duty]

    asyncio.run(run())


def test_tracker_inconsistent_parsigs():
    """Same duty/pubkey with partials under DIFFERENT message roots is
    reported and counted; threshold failures then carry the
    bug_par_sig_db_inconsistent reason — except sync-message duties,
    where disagreement is a known limitation
    (ref: tracker.go:59-71 parsigsByMsg, reason.go:136,160)."""

    async def run():
        tr = Tracker(peer_share_indices=[1, 2, 3, 4])
        duty = Duty(7, DutyType.ATTESTER)
        pk = "0xaa"
        for s in (
            Step.SCHEDULER,
            Step.FETCHER,
            Step.CONSENSUS,
            Step.DUTY_DB,
            Step.VALIDATOR_API,
            Step.PARSIG_DB_INTERNAL,
            Step.PARSIG_EX,
        ):
            tr.step_event(duty, s)
        tr.duty_scheduled(duty, [pk])
        tr.partial_observed(duty, 1, pubkey=pk, root=b"r1" * 16)
        tr.partial_observed(duty, 2, pubkey=pk, root=b"r2" * 16)  # mismatch!
        tr.partial_observed(duty, 3, pubkey=pk, root=b"r1" * 16)
        report = await tr.duty_expired(duty)
        assert report.failed_step == Step.PARSIG_DB_THRESHOLD
        assert report.reason == Reason.PARSIG_INCONSISTENT
        assert report.inconsistent_pubkeys == [pk]
        assert tr.inconsistent_total[DutyType.ATTESTER] == 1

        # sync-message duties downgrade to the known-limitation reason
        sduty = Duty(8, DutyType.SYNC_MESSAGE)
        for s in (
            Step.SCHEDULER,
            Step.FETCHER,
            Step.CONSENSUS,
            Step.DUTY_DB,
            Step.VALIDATOR_API,
            Step.PARSIG_DB_INTERNAL,
            Step.PARSIG_EX,
        ):
            tr.step_event(sduty, s)
        tr.duty_scheduled(sduty, [pk])
        tr.partial_observed(sduty, 1, pubkey=pk, root=b"x1" * 16)
        tr.partial_observed(sduty, 2, pubkey=pk, root=b"x2" * 16)
        sreport = await tr.duty_expired(sduty)
        assert sreport.reason == Reason.PARSIG_INCONSISTENT_SYNC

    asyncio.run(run())


def test_tracker_unexpected_peer():
    """A partial for a validator with NO scheduled definition counts as
    unexpected-peer participation, not normal participation
    (ref: tracker.go:539-573 analyseParticipation)."""

    async def run():
        tr = Tracker(peer_share_indices=[1, 2, 3, 4])
        duty = Duty(9, DutyType.ATTESTER)
        for s in Step:
            tr.step_event(duty, s)
        tr.duty_scheduled(duty, ["0xaa", "0xbb"])
        tr.partial_observed(duty, 1, pubkey="0xaa", root=b"r" * 16)
        tr.partial_observed(duty, 2, pubkey="0xbb", root=b"r" * 16)
        # share 3 submits for a validator this cluster never scheduled
        tr.partial_observed(duty, 3, pubkey="0xEVIL", root=b"r" * 16)
        report = await tr.duty_expired(duty)
        assert report.success
        assert report.unexpected_shares == {3: 1}
        assert tr.unexpected_total[3] == 1
        assert report.participation_counts == {1: 1, 2: 1}
        assert report.expected_per_peer == 2
        assert report.participation[3] is False

        # exit-style duties are never classified unexpected
        eduty = Duty(9, DutyType.EXIT)
        for s in Step:
            tr.step_event(eduty, s)
        tr.partial_observed(eduty, 3, pubkey="0xcc", root=b"r" * 16)
        ereport = await tr.duty_expired(eduty)
        assert ereport.unexpected_shares == {}

    asyncio.run(run())


def test_tracker_prerequisite_attribution():
    """A proposer duty stuck at fetch when the slot's randao duty failed
    is attributed to the randao failure
    (ref: tracker.go analyseFetcherFailedProposer)."""

    async def run():
        tr = Tracker(peer_share_indices=[1, 2, 3, 4])
        randao = Duty(11, DutyType.RANDAO)
        tr.step_event(randao, Step.SCHEDULER)
        tr.step_event(randao, Step.FETCHER)
        rrep = await tr.duty_expired(randao)
        assert not rrep.success

        proposer = Duty(11, DutyType.PROPOSER)
        tr.step_event(proposer, Step.SCHEDULER)  # fetch never completed
        # the fetch RAISED (normal path: awaiting the randao aggregate
        # fails) — prerequisite attribution still wins over the
        # BN-error classification
        tr.step_failed(proposer, Step.FETCHER, RuntimeError("agg timeout"))
        prep = await tr.duty_expired(proposer)
        assert prep.failed_step == Step.FETCHER
        assert prep.reason == Reason.RANDAO_FAILED

        # expiry ORDER must not matter: the proposer often expires BEFORE
        # its randao (same deadline, Duty ordering ties) — the live event
        # set of the un-analysed randao is judged instead
        randao2 = Duty(20, DutyType.RANDAO)
        tr.step_event(randao2, Step.SCHEDULER)  # stuck at fetch, unexpired
        prop2 = Duty(20, DutyType.PROPOSER)
        tr.step_event(prop2, Step.SCHEDULER)
        tr.step_failed(prop2, Step.FETCHER, RuntimeError("agg timeout"))
        prep2 = await tr.duty_expired(prop2)  # proposer analysed first
        assert prep2.reason == Reason.RANDAO_FAILED

        # ...and a SUCCESSFUL live randao (terminal = aggregate store,
        # randao never broadcasts) must NOT be blamed
        randao3 = Duty(21, DutyType.RANDAO)
        for s in Step:
            if s <= Step.AGG_SIG_DB:
                tr.step_event(randao3, s)
        prop3 = Duty(21, DutyType.PROPOSER)
        tr.step_event(prop3, Step.SCHEDULER)
        tr.step_failed(prop3, Step.FETCHER, RuntimeError("http 500"))
        prep3 = await tr.duty_expired(prop3)
        assert prep3.reason == Reason.FETCH_BN_ERROR
        # and when that randao expires it is reported SUCCESSFUL
        rrep3 = await tr.duty_expired(randao3)
        assert rrep3.success
        # success memory: a later same-slot proposer check still clears it
        assert not tr._prereq_failed(randao3)

        # a plain attester fetch error (no prerequisite) is a BN error
        att = Duty(12, DutyType.ATTESTER)
        tr.step_event(att, Step.SCHEDULER)
        tr.step_failed(att, Step.FETCHER, RuntimeError("http 500"))
        arep = await tr.duty_expired(att)
        assert arep.reason == Reason.FETCH_BN_ERROR
        # and a silent fetch stall is the bug-class reason
        att2 = Duty(13, DutyType.ATTESTER)
        tr.step_event(att2, Step.SCHEDULER)
        arep2 = await tr.duty_expired(att2)
        assert arep2.reason == Reason.FETCH_FAILED

    asyncio.run(run())


def test_tracking_edge_collects_parsig_metadata():
    """The wire option records scheduled pubkeys from fetcher.fetch and
    (pubkey, share, root) triples from parsigdb stores."""

    async def run():
        from dataclasses import dataclass

        tr = Tracker(peer_share_indices=[1, 2])
        duty = Duty(6, DutyType.ATTESTER)

        async def fetch(duty, defs):
            return None

        await tracking(tr)("fetcher.fetch", fetch)(duty, {"0xaa": object()})
        assert tr._expected[duty] == {"0xaa"}

        @dataclass
        class FakePsig:
            share_idx: int
            data: object = None

        async def store(duty, psigs):
            return None

        await tracking(tr)("parsigdb.store_external", store)(
            duty, {"0xaa": FakePsig(2)}
        )
        roots = tr._parsigs[duty]["0xaa"]
        assert len(roots) == 1 and 2 in next(iter(roots.values()))

    asyncio.run(run())


def test_forkjoin_bounded_order_and_failures():
    """ref: app/forkjoin/forkjoin.go — bounded fan-out, input order,
    per-input failure capture."""
    import asyncio

    from charon_tpu.app.forkjoin import flatten, forkjoin

    async def main():
        concurrent, peak = 0, 0

        async def work(x):
            nonlocal concurrent, peak
            concurrent += 1
            peak = max(peak, concurrent)
            await asyncio.sleep(0.01)
            concurrent -= 1
            if x == 5:
                raise ValueError("boom")
            return x * 10

        results = await forkjoin(list(range(12)), work, workers=3)
        assert peak <= 3
        assert [r.input for r in results] == list(range(12))
        assert results[5].error is not None and not results[5].ok
        assert [r.output for r in results if r.ok] == [
            x * 10 for x in range(12) if x != 5
        ]
        try:
            flatten(results)
        except ValueError as e:
            assert str(e) == "boom"
        else:
            raise AssertionError("flatten must raise the first failure")
        ok = await forkjoin([1, 2], work)
        assert flatten(ok) == [10, 20]

    asyncio.run(main())


def test_structured_errors():
    """ref: app/errors + app/z — fields, wrapping, chain aggregation,
    sentinels, stacks without raising."""
    from charon_tpu.app import errors

    base = errors.new("dial failed", addr="1.2.3.4:9000")
    wrapped = errors.wrap(base, "peer unreachable", peer=3, addr="outer")
    # outermost layer wins on conflicts; inner context preserved
    assert errors.fields_of(wrapped) == {"peer": 3, "addr": "outer"}
    assert "peer=3" in str(wrapped)
    # sentinel matching through the chain
    sent = errors.sentinel("not found")
    assert errors.is_any(errors.wrap(sent, "lookup failed", key="k"), sent)
    assert not errors.is_any(wrapped, sent)
    # stack available without ever raising (construct-and-log pattern)
    assert "test_structured_errors" in base.stack()
    # raised errors report the real traceback
    try:
        raise errors.new("boom", x=1)
    except errors.StructuredError as e:
        assert "raise errors.new" in e.stack()
        assert errors.fields_of(e) == {"x": 1}
    # implicit context (raise inside except) also aggregates
    try:
        try:
            raise errors.new("inner", a=1)
        except errors.StructuredError:
            raise errors.new("outer", b=2)
    except errors.StructuredError as e2:
        assert errors.fields_of(e2) == {"a": 1, "b": 2}
    # ...but `raise B from None` suppresses the context, so a handled
    # unrelated failure's fields don't misattribute into B's log line
    try:
        try:
            raise errors.new("handled fallback", addr="wrong-peer")
        except errors.StructuredError:
            raise errors.new("real failure", b=2) from None
    except errors.StructuredError as e3:
        assert errors.fields_of(e3) == {"b": 2}


def test_pprof_endpoints():
    """pprof-analogue debug endpoints on the monitoring API
    (ref: app/monitoringapi.go:47 net/http/pprof registration)."""

    async def run():
        m = ClusterMetrics("0xhash", "c", "node0")
        server = await serve_monitoring("127.0.0.1", 0, m)
        port = server.sockets[0].getsockname()[1]

        async def get(path):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.1\r\n\r\n".encode())
            await writer.drain()
            data = await reader.read()
            writer.close()
            return data

        prof = await get("/debug/pprof/profile?seconds=0.2")
        assert b"200 OK" in prof and b"cumulative" in prof
        # malformed / non-finite durations are a 400, not a dropped conn
        assert b"400 Bad Request" in await get("/debug/pprof/profile?seconds=abc")
        assert b"400 Bad Request" in await get("/debug/pprof/profile?seconds=nan")

        threads = await get("/debug/pprof/threads")
        assert b"200 OK" in threads and b"--- thread" in threads

        # heap tracing NEVER arms implicitly (allocation overhead):
        # explicit start/snapshot/stop protocol
        assert b"not armed" in await get("/debug/pprof/heap")
        assert b"armed" in await get("/debug/pprof/heap?start=1")
        snap = await get("/debug/pprof/heap")
        assert b"200 OK" in snap and b"size=" in snap
        assert b"stopped" in await get("/debug/pprof/heap?stop=1")
        import tracemalloc

        assert not tracemalloc.is_tracing()
        server.close()
        await server.wait_closed()

    asyncio.run(run())


def test_otlp_exporter_end_to_end():
    """Spans recorded through a Tracer with an OTLPExporter arrive at a
    local OTLP/HTTP collector in the standard JSON encoding
    (ref: app/tracer/trace.go:40-124 exports OTLP to Jaeger)."""
    import http.server
    import json
    import threading

    from charon_tpu.app import tracer as trc

    received = []
    got = threading.Event()

    class Collector(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            received.append((self.path, json.loads(body)))
            got.set()
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Collector)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        exporter = trc.OTLPExporter(
            f"http://127.0.0.1:{srv.server_address[1]}",
            service_name="charon-tpu-test",
            flush_interval=0.2,
        )
        t = trc.Tracer(exporter=exporter)
        duty = Duty(slot=7, type=DutyType.ATTESTER)
        with trc.span("fetcher", duty=duty, tracer=t, share=3):
            with trc.span("consensus", tracer=t):
                pass
        with pytest.raises(RuntimeError):
            with trc.span("sigagg", duty=duty, tracer=t):
                raise RuntimeError("boom")
        assert got.wait(5.0), "collector never received a batch"
        exporter.shutdown()

        path, payload = received[0]
        assert path == "/v1/traces"
        rs = payload["resourceSpans"][0]
        res_attrs = {
            a["key"]: a["value"]["stringValue"]
            for a in rs["resource"]["attributes"]
        }
        assert res_attrs["service.name"] == "charon-tpu-test"
        spans = [
            s
            for batch in received
            for s in batch[1]["resourceSpans"][0]["scopeSpans"][0]["spans"]
        ]
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"fetcher", "consensus", "sigagg"}
        fetcher, consensus = by_name["fetcher"], by_name["consensus"]
        # duty-rooted deterministic trace id, child nests under parent
        assert fetcher["traceId"] == trc.duty_trace_id(duty)
        assert consensus["traceId"] == fetcher["traceId"]
        assert consensus["parentSpanId"] == fetcher["spanId"]
        assert len(fetcher["traceId"]) == 32 and len(fetcher["spanId"]) == 16
        # OTLP status codes: OK=1, ERROR=2; nanosecond string timestamps
        assert fetcher["status"]["code"] == 1
        assert by_name["sigagg"]["status"]["code"] == 2
        assert int(fetcher["endTimeUnixNano"]) >= int(
            fetcher["startTimeUnixNano"]
        )
        attrs = {a["key"]: a["value"] for a in fetcher["attributes"]}
        assert attrs["share"] == {"intValue": "3"}
        assert exporter.exported == 3 and exporter.dropped == 0
    finally:
        srv.shutdown()


def test_otlp_exporter_dead_collector_drops():
    """A dead collector must never stall recording — spans are counted
    dropped and the caller is unaffected."""
    from charon_tpu.app import tracer as trc

    exporter = trc.OTLPExporter(
        "http://127.0.0.1:1", flush_interval=0.1, batch_size=1
    )
    t = trc.Tracer(exporter=exporter)
    with trc.span("step", tracer=t):
        pass
    exporter.shutdown()
    assert exporter.dropped >= 1 and exporter.exported == 0


def test_tracker_per_pubkey_failure_attribution():
    """Per-validator attribution (ref: the reference analyses events per
    (duty, pubkey)): an expected pubkey whose partials never reached
    threshold is reported individually, even when the duty as a whole
    succeeded for the other validators."""
    from charon_tpu.core.types import pubkey_from_bytes

    async def run():
        pk_ok = pubkey_from_bytes(b"\x01" * 48)
        pk_short = pubkey_from_bytes(b"\x02" * 48)
        pk_silent = pubkey_from_bytes(b"\x03" * 48)
        duty = Duty(9, DutyType.ATTESTER)
        tr = Tracker(peer_share_indices=[1, 2, 3, 4], threshold=3)
        tr.duty_scheduled(duty, [pk_ok, pk_short, pk_silent])
        for s in Step:
            tr.step_event(duty, s)  # duty-level success
        for idx in (1, 2, 3):
            tr.partial_observed(duty, idx, pubkey=pk_ok, root=b"r")
        tr.partial_observed(duty, 1, pubkey=pk_short, root=b"r")
        report = await tr.duty_expired(duty)
        assert report.success  # the duty (pk_ok) succeeded...
        assert report.failed_pubkeys == {
            pk_short: Reason.INSUFFICIENT_PARTIALS,  # 1 < threshold 3
            pk_silent: Reason.NO_LOCAL_PARTIAL,  # zero partials
        }
        assert tr.pubkey_failures_total[DutyType.ATTESTER] == 2

        # before the signing phase (no DUTY_DB step) nothing is
        # attributed per pubkey — the duty-level reason covers it
        duty2 = Duty(10, DutyType.ATTESTER)
        tr.duty_scheduled(duty2, [pk_ok])
        tr.step_event(duty2, Step.SCHEDULER)
        report2 = await tr.duty_expired(duty2)
        assert report2.failed_pubkeys == {}

    asyncio.run(run())


def test_tracker_per_pubkey_split_roots_flagged_inconsistent():
    """Shares split across conflicting message roots can never
    aggregate even if their union reaches threshold — attributed as
    inconsistency, not missed (review r5: union-counting hid exactly
    the inconsistency case)."""
    from charon_tpu.core.types import pubkey_from_bytes

    async def run():
        pk = pubkey_from_bytes(b"\x04" * 48)
        duty = Duty(11, DutyType.ATTESTER)
        tr = Tracker(peer_share_indices=[1, 2, 3, 4], threshold=3)
        tr.duty_scheduled(duty, [pk])
        for s in Step:
            tr.step_event(duty, s)
        # {1,2} on root A, {3} on root B: union 3 >= threshold but no
        # single root can aggregate
        tr.partial_observed(duty, 1, pubkey=pk, root=b"A")
        tr.partial_observed(duty, 2, pubkey=pk, root=b"A")
        tr.partial_observed(duty, 3, pubkey=pk, root=b"B")
        report = await tr.duty_expired(duty)
        assert report.failed_pubkeys == {pk: Reason.PARSIG_INCONSISTENT}

        # sync-committee duties expect disagreement: distinct reason
        duty2 = Duty(12, DutyType.SYNC_MESSAGE)
        tr.duty_scheduled(duty2, [pk])
        for s in Step:
            tr.step_event(duty2, s)
        tr.partial_observed(duty2, 1, pubkey=pk, root=b"A")
        tr.partial_observed(duty2, 2, pubkey=pk, root=b"A")
        tr.partial_observed(duty2, 3, pubkey=pk, root=b"B")
        report2 = await tr.duty_expired(duty2)
        assert report2.failed_pubkeys == {
            pk: Reason.PARSIG_INCONSISTENT_SYNC
        }

    asyncio.run(run())
