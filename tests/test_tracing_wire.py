"""Duty-rooted distributed tracing across the wire (ISSUE 4 tentpole).

Covers: one span per wire edge per duty with correct parentage
(core/wire.tracing), the cross-node merge of per-node JSONL exports
into one trace per duty via the deterministic duty trace ids, and
trace-context round-trips through transport frames — including a
corrupted-frame chaos transport, which must fall back to a fresh
duty-rooted root span without ever crashing the receive path.
"""

from __future__ import annotations

import asyncio

import pytest

from charon_tpu import tbls
from charon_tpu.app import tracer
from charon_tpu.core import qbft
from charon_tpu.core.types import Duty, DutyType
from charon_tpu.core.wire import tracing
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.waiting import wait_progress, wait_until

# the wire edges every completed attestation duty must traverse,
# in pipeline order (core/wire.wire subscription graph)
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


def test_every_wire_edge_produces_one_span_with_parentage():
    """A duty flowing through a chain of wrapped edges leaves exactly
    one span per edge, each nested under the edge that invoked it, all
    in the duty's deterministic trace."""
    t = tracer.Tracer()
    opt = tracing(t)
    duty = Duty(slot=11, type=DutyType.ATTESTER)

    async def leaf(d, *args):
        return None

    fn = leaf
    for name in reversed(WIRE_EDGES):
        wrapped_next = opt(name, fn)

        async def body(d, *args, _n=wrapped_next):
            return await _n(d, {"0xab": object()})

        fn = body

    asyncio.run(fn(duty))

    spans = t.dump()
    by_name = {s["name"]: s for s in spans}
    assert sorted(by_name) == sorted(WIRE_EDGES)
    assert len(spans) == len(WIRE_EDGES)  # exactly one span per edge
    tid = tracer.duty_trace_id(duty)
    for s in spans:
        assert s["trace_id"] == tid
        assert s["attrs"]["duty"] == str(duty)
        assert s["attrs"]["slot"] == duty.slot
        assert s["attrs"]["pubkeys"] == 1
    # parentage follows the pipeline: each edge nests under its caller
    assert by_name[WIRE_EDGES[0]]["parent_id"] == ""
    for parent, child in zip(WIRE_EDGES, WIRE_EDGES[1:]):
        assert by_name[child]["parent_id"] == by_name[parent]["span_id"]


def test_parsigex_receive_joins_remote_trace():
    """A valid propagated frame context parents the receive span under
    the sender's broadcast span — cross-node parentage."""
    from charon_tpu.core.parsigex import MemTransport, ParSigEx

    t = tracer.Tracer()
    duty = Duty(slot=5, type=DutyType.ATTESTER)
    psx = ParSigEx(1, MemTransport(), tracer=t)
    remote_trace, remote_span = "ab" * 16, "cd" * 8

    asyncio.run(
        psx.receive(duty, {}, tctx=f"{remote_trace}-{remote_span}")
    )
    (s,) = t.dump()
    assert s["name"] == "parsigex.receive"
    assert s["trace_id"] == remote_trace
    assert s["parent_id"] == remote_span


@pytest.mark.parametrize(
    "garbage",
    [
        "",
        "zz",
        "nothex" * 8 + "-" + "zz" * 8,
        "ab" * 16,
        42,
        b"ab" * 16,
        None,
        # right lengths but not strict hex: int(x, 16) would accept
        # these prefix/whitespace forms — parse_ctx must not
        "0x" + "ab" * 15 + "-" + "0x" + "cd" * 7,
        " " + "ab" * 15 + "a-" + "+" + "cd" * 7 + "c",
    ],
)
def test_parsigex_receive_corrupt_ctx_falls_back_to_root(garbage):
    """ANY malformed trace context decodes to None: the receive span
    roots a fresh duty trace and delivery proceeds."""
    from charon_tpu.core.parsigex import MemTransport, ParSigEx

    t = tracer.Tracer()
    duty = Duty(slot=6, type=DutyType.ATTESTER)
    psx = ParSigEx(1, MemTransport(), tracer=t)
    delivered = []

    async def sub(d, s):
        delivered.append(d)

    psx.subscribe(sub)
    asyncio.run(psx.receive(duty, {}, tctx=garbage))
    assert delivered == [duty]
    (s,) = t.dump()
    assert s["parent_id"] == ""
    assert s["trace_id"] == tracer.duty_trace_id(duty)


def test_chaos_corrupted_frame_ctx_never_crashes():
    """Through the chaos transport with corrupt=1.0 every frame's trace
    context arrives mangled: receivers must record fresh duty-rooted
    root spans and never raise."""
    from charon_tpu.core.parsigex import ParSigEx
    from charon_tpu.testutil.chaos import ChaosConfig, ChaosParSigTransport

    async def run():
        transport = ChaosParSigTransport(ChaosConfig(seed=7, corrupt=1.0))
        tracers = [tracer.Tracer(), tracer.Tracer()]
        nodes = [
            ParSigEx(i + 1, transport, tracer=tracers[i]) for i in range(2)
        ]
        duty = Duty(slot=3, type=DutyType.ATTESTER)
        with tracer.span("parsigex.broadcast", duty=duty, tracer=tracers[0]):
            await transport.send(1, duty, {}, tctx=tracer.encode_ctx())
        def received():
            return [s for s in tracers[1].dump() if s["name"] == "parsigex.receive"]

        await wait_until(
                received,
                "the corrupted frame's delivery (the chaos delivery tasks)",
            )
        assert transport.corrupted >= 1
        recv = received()
        for s in recv:
            # fallback: fresh duty-rooted root, NOT the sender's span
            assert s["parent_id"] == ""
            assert s["trace_id"] == tracer.duty_trace_id(duty)
        assert nodes is not None

    asyncio.run(run())


def test_qbft_deliver_ctx_propagation_and_fallback():
    """QBFT frames carry trace context; a follower's message-handling
    span joins the sender's trace, and garbage context falls back to a
    fresh duty-rooted root without crashing delivery."""
    from charon_tpu.core.consensus_qbft import MemMsgNet, QBFTConsensus

    async def run():
        t = tracer.Tracer()
        node = QBFTConsensus(MemMsgNet(), nodes=4, tracer=t)
        duty = Duty(slot=9, type=DutyType.ATTESTER)
        msg = qbft.Msg(
            type=qbft.MsgType.PRE_PREPARE,
            instance=duty,
            source=1,
            round=1,
            value=b"\x01" * 32,
        )
        node.deliver(duty, msg, {}, tctx="ab" * 16 + "-" + "cd" * 8)
        node.deliver(duty, msg, {}, tctx="garbage")
        spans = [s for s in t.dump() if s["name"] == "qbft.deliver"]
        assert len(spans) == 2
        assert spans[0]["trace_id"] == "ab" * 16
        assert spans[0]["parent_id"] == "cd" * 8
        assert spans[0]["attrs"]["msg_type"] == "PRE_PREPARE"
        assert spans[1]["trace_id"] == tracer.duty_trace_id(duty)
        assert spans[1]["parent_id"] == ""
        node.trim(duty)

    asyncio.run(run())


# -- cross-node simnet merge --------------------------------------------------


@pytest.fixture()
def host_tbls():
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


def _completed_attester_slots(beacon, n: int) -> list[int]:
    by_slot: dict[int, int] = {}
    for a in beacon.attestations:
        by_slot[a.data.slot] = by_slot.get(a.data.slot, 0) + 1
    return sorted(s for s, c in by_slot.items() if c >= n)


def test_simnet_cross_node_traces_merge(host_tbls, tmp_path):
    """4 nodes, >= 2 attestation duties: per-node JSONL exports merge
    into ONE duty-rooted trace per duty covering every wire edge plus
    the crypto plane's decode/pack/device stages, with spans from all
    4 nodes and no orphan parentage."""
    from charon_tpu.testutil.simnet import build_cluster

    cluster = build_cluster(
        n=4,
        t=3,
        slot_duration=0.2,
        tracing_on=True,
        trace_dir=str(tmp_path),
        crypto_plane=True,
    )

    async def drive():
        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:

            await wait_progress(
                lambda: len(_completed_attester_slots(cluster.beacon, 4)) >= 2,
                probe=lambda: len(cluster.beacon.attestations),
                what="two slots all four nodes broadcast",
            )
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)
            # let in-flight crypto-plane flushes settle before close
            await asyncio.sleep(0.1)

    asyncio.run(drive())
    cluster.close()

    paths = cluster.trace_paths()
    assert len(paths) == 4
    per_node = [tracer.merge_jsonl([p]) for p in paths]
    merged = tracer.merge_jsonl(paths)

    slots = _completed_attester_slots(cluster.beacon, 4)[:2]
    assert len(slots) == 2
    for slot in slots:
        duty = Duty(slot=slot, type=DutyType.ATTESTER)
        tid = tracer.duty_trace_id(duty)
        # ONE trace per duty: every span tagged with this duty carries
        # the deterministic trace id, on every node
        duty_spans = [
            s for s in merged if s["attrs"].get("duty") == str(duty)
        ]
        assert duty_spans
        assert {s["trace_id"] for s in duty_spans} == {tid}
        trace = [s for s in merged if s["trace_id"] == tid]
        names = {s["name"] for s in trace}
        for edge in WIRE_EDGES:
            assert edge in names, f"missing {edge} for slot {slot}"
        # crypto-plane stages bridged into the duty trace
        for stage in (
            "cryptoplane.flush",
            "cryptoplane.decode",
            "cryptoplane.device",
        ):
            assert stage in names, f"missing {stage} for slot {slot}"
        # all 4 nodes contributed spans to the SAME trace
        for i, spans in enumerate(per_node):
            assert any(
                s["trace_id"] == tid for s in spans
            ), f"node{i + 1} contributed no spans to slot {slot}"
        # no orphans: every parent id resolves inside the merged trace
        ids = {s["span_id"] for s in trace}
        for s in trace:
            assert s["parent_id"] == "" or s["parent_id"] in ids, (
                f"orphan span {s['name']} in slot {slot}"
            )
        # timeline assembly works off the merged export too
        timelines = tracer.duty_timeline(slot, spans=merged)
        assert any(tl["trace_id"] == tid for tl in timelines)


# -- the served path's own spans (ISSUE 26) -----------------------------------
#
# Each is opened and closed where the work happens, as a child of the span
# that caused it, with wall-clock ends (time.time(), the clock a device
# profile is anchored to).

import time

from charon_tpu.eth2util.signing import ForkInfo

_FORK = ForkInfo(b"\x11" * 32, b"\x00" * 4, b"\x00" * 4)


class _YesPlane:
    """Stands where the tenant's handle stands: every lane passes."""

    t = 2

    async def verify(self, items, deadline=None):
        await asyncio.sleep(0.01)
        return [True] * len(items)


def _lane():
    from charon_tpu.crypto import g1g2

    return (g1g2.g1_to_bytes(g1g2.G1_GEN), b"\x07" * 32, g1g2.g2_to_bytes(g1g2.G2_GEN))


def _service(t, window=0.03, **coalescer):
    from charon_tpu.core.cryptoplane import SlotCoalescer
    from charon_tpu.core.cryptosvc import CryptoPlaneService
    from charon_tpu.testutil.simnet import SimHostPlane

    coal = SlotCoalescer(
        SimHostPlane(2), window=window, decode_workers=0,
        stats_hook=tracer.plane_span_bridge(t), **coalescer)
    svc = CryptoPlaneService(coal, tracer=t)
    return coal, svc, svc.register("tenant-a")


async def _vapi_submit(t, duty):
    from charon_tpu.core.types import PubKey
    from charon_tpu.core.validatorapi import ValidatorAPI

    pk = PubKey("0x" + "ab" * 48)
    vapi = ValidatorAPI(1, {pk: b"\x01" * 48}, _FORK, plane=_YesPlane(), tracer=t)
    vapi.subscribe(tracing(t)("parsigdb.store_internal", _noop))
    await vapi.submit_randao(duty.slot, pk, b"\x02" * 96)
    return Duty(duty.slot, DutyType.RANDAO)


async def _noop(*_a, **_k):
    return None


async def _parsigex_verify(t, duty):
    from charon_tpu.core.parsigex import MemTransport, ParSigEx

    class Verifier:
        async def verify_async(self, d, s):
            await asyncio.sleep(0.01)
            return True

    psx = ParSigEx(1, MemTransport(), Verifier(), tracer=t)
    await psx.receive(duty, {"0xaa": object(), "0xbb": object()})
    return duty


async def _qbft_instance(t, duty):
    from charon_tpu.core.consensus_qbft import MemMsgNet, QBFTConsensus

    net = MemMsgNet()
    nodes = [QBFTConsensus(net, 4, tracer=t if i == 0 else None) for i in range(4)]
    propose = [tracing(t if i == 0 else None)("consensus.propose", n.propose)
               for i, n in enumerate(nodes)]
    await asyncio.wait_for(
        asyncio.gather(*(p(duty, {"0xaa": f"v{i}"}) for i, p in enumerate(propose))), 10)
    return duty


async def _svc_queue(t, duty):
    coal, svc, plane = _service(t)
    with tracer.span("parsigex.verify", duty=duty, tracer=t):
        assert await plane.verify([_lane()]) == [True]
    svc.close()
    coal.close()
    return duty


_SPANS = {
    # name: (driver, parent's name(s), attributes the span must carry)
    "vapi.submit": (_vapi_submit, ("",), {"duty_type": "randao", "count": 1, "rejected": 0}),
    "parsigex.verify": (_parsigex_verify, ("parsigex.receive",), {"pubkeys": 2, "ok": True}),
    "qbft.instance": (_qbft_instance, ("consensus.propose", "qbft.deliver"), {"round": 1}),
    "cryptosvc.queue": (_svc_queue, ("parsigex.verify",),
                        {"tenant": "tenant-a", "lanes": 1, "kind": "verify", "shed": False}),
    "cryptoplane.window": (_svc_queue, ("parsigex.verify",),
                           {"window": 0.03, "jobs": 1, "lanes": 1, "closed_by": "timer"}),
}


@pytest.mark.parametrize("name", sorted(_SPANS))
def test_the_served_paths_own_spans(name):
    driver, parents, attrs = _SPANS[name]
    t = tracer.Tracer()
    t0 = time.time()
    duty = asyncio.run(driver(t, Duty(slot=21, type=DutyType.ATTESTER)))
    t1 = time.time()
    spans = {s.span_id: s for s in t.spans}
    mine = [s for s in spans.values() if s.name == name]
    assert len(mine) == 1, [s.name for s in spans.values()]
    (s,) = mine
    assert s.trace_id == tracer.duty_trace_id(duty)  # all of one duty, one trace
    parent = spans.get(s.parent_id)
    assert (parent.name if parent else "") in parents
    if parent is not None:
        assert parent.trace_id == s.trace_id
    for key, value in attrs.items():
        assert s.attrs[key] == value, (key, s.attrs)
    assert s.status == "ok"
    assert t0 <= s.start <= s.end <= t1  # the wall clock, at both ends
    if name == "qbft.instance":
        # first sign of life -> decided; the three bare participants beside
        # it recorded into the process-global tracer, not into this node's
        assert s.attrs["messages"] >= 1 and s.end - s.start < 5
        assert [x.name for x in spans.values()].count("qbft.instance") == 1
    if name == "vapi.submit":
        # request parsed -> the subscriber returned: the store edge nests in it
        (store,) = [x for x in spans.values() if x.name == "parsigdb.store_internal"]
        assert store.parent_id == s.span_id and s.start <= store.start <= store.end <= s.end
    if name == "cryptoplane.window":
        (queue,) = [x for x in spans.values() if x.name == "cryptosvc.queue"]
        (flush,) = [x for x in spans.values() if x.name == "cryptoplane.flush"]
        assert queue.parent_id == flush.parent_id == s.parent_id  # siblings, as caused
        assert queue.end <= s.start + 1e-3 and 0.02 < s.end - s.start < 5


def test_vapi_submit_counts_what_the_batch_rejected():
    from charon_tpu.core.types import PubKey
    from charon_tpu.core.validatorapi import ValidatorAPI, VapiError

    class NoPlane(_YesPlane):
        async def verify(self, items, deadline=None):
            return [False] * len(items)

    t = tracer.Tracer()
    pk = PubKey("0x" + "ab" * 48)
    vapi = ValidatorAPI(1, {pk: b"\x01" * 48}, _FORK, plane=NoPlane(), tracer=t)
    with pytest.raises(VapiError):
        asyncio.run(vapi.submit_randao(3, pk, b"\x02" * 96))
    (s,) = t.spans
    assert (s.name, s.status, s.attrs["count"], s.attrs["rejected"]) == ("vapi.submit", "error", 1, 1)


def test_qbft_instance_that_runs_out_of_time_ends_in_error():
    from charon_tpu.core.consensus_qbft import MemMsgNet, QBFTConsensus

    async def run():
        t = tracer.Tracer()
        node = QBFTConsensus(MemMsgNet(), 4, tracer=t)  # alone: no quorum ever
        duty = Duty(slot=30, type=DutyType.ATTESTER)
        task = asyncio.create_task(node.propose(duty, {"0xaa": "v"}))
        await asyncio.sleep(0.05)
        node.trim(duty)  # the Deadliner's hook
        await asyncio.gather(task, return_exceptions=True)
        (s,) = [s for s in t.spans if s.name == "qbft.instance"]
        assert s.status == "error" and "Cancelled" in s.attrs["error"]
        assert s.end - s.start >= 0.04 and duty not in node._seen

    asyncio.run(run())


def test_a_shed_submission_leaves_a_zero_length_queue_span():
    from charon_tpu.core.cryptosvc import PlaneOverloadError, TenantQuota

    async def run():
        t = tracer.Tracer()
        coal, svc, _ = _service(t)
        tight = svc.register("tenant-b", TenantQuota(max_queue_lanes=1))
        with tracer.span("vapi.submit", tracer=t) as parent:
            with pytest.raises(PlaneOverloadError):
                await tight.verify([_lane(), _lane()])
        (s,) = [s for s in t.spans if s.name == "cryptosvc.queue"]
        assert s.attrs["shed"] is True and s.attrs["lanes"] == 2 and s.end == s.start
        assert s.parent_id == parent.span_id
        svc.close()
        coal.close()

    asyncio.run(run())


def test_each_submission_runs_under_its_own_span_not_the_dispatchers():
    """The service's dispatcher task is started by whichever submission
    comes first; a later one must still be bridged under ITS span."""

    async def run():
        t = tracer.Tracer()
        coal, svc, plane = _service(t, window=0.05)

        async def submit(name, delay):
            await asyncio.sleep(delay)
            with tracer.span(name, tracer=t) as s:
                await plane.verify([_lane()])
            return s

        first, second = await asyncio.gather(submit("first", 0.0), submit("second", 0.01))
        for parent in (first, second):
            kids = sorted(s.name for s in t.spans if s.parent_id == parent.span_id)
            assert kids == ["cryptoplane.flush", "cryptoplane.window", "cryptosvc.queue"], kids
        svc.close()
        coal.close()

    asyncio.run(run())


@pytest.mark.parametrize("closed_by", ["timer", "pulled_earlier", "deadline", "complete"])
def test_cryptoplane_window_says_what_closed_it(closed_by):
    async def run():
        t = tracer.Tracer()
        coal, svc, plane = _service(t, window=1.0, window_max=1.0)
        near = time.time() + 2.0  # the graded shrink: 1 % of what is left
        if closed_by == "timer":
            await plane.verify([_lane()])
        elif closed_by == "complete":
            # the wave's two sets: the second makes it whole
            wave = (("5/attester", 2),)
            await asyncio.gather(
                plane.verify([_lane()], wave=wave), plane.verify([_lane()], wave=wave))
        elif closed_by == "deadline":
            await plane.verify([_lane()], deadline=near)
        else:
            async def later():
                await asyncio.sleep(0.03)
                await plane.verify([_lane()], deadline=near)

            await asyncio.gather(plane.verify([_lane()]), later())
        svc.close()
        coal.close()
        windows = [s for s in t.spans if s.name == "cryptoplane.window"
                   and not s.attrs.get("shared")]
        assert len(windows) == 1
        assert coal.windows_closed == {closed_by: 1}
        return windows[0]

    w = asyncio.run(run())
    assert w.attrs["closed_by"] == closed_by and w.attrs["window"] == 1.0
    waited = w.end - w.start
    # `window` is what was configured, the span is what the first job
    # waited (margins for a loaded machine: the shrunk windows are ~20 ms)
    assert (waited > 0.99) if closed_by == "timer" else (waited < 0.7)
