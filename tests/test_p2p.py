"""P2P TCP mesh: handshake gating, request/response, ping, codec
round-trips, and QBFT + parsigex running over real localhost sockets."""

import asyncio
import socket

import pytest

# the node-identity stack (app/k1util, eth2util/keystore) needs the
# optional `cryptography` package; skip LOUDLY where absent instead
# of erroring at collection (ISSUE 17 satellite — no test deleted)
pytest.importorskip(
    "cryptography",
    reason="app.k1util requires the optional 'cryptography' package",
)

from charon_tpu.app import k1util
from charon_tpu.core import qbft
from charon_tpu.core.consensus_qbft import QBFTConsensus
from charon_tpu.core.eth2data import ParSignedData, SignedData
from charon_tpu.core.parsigex import ParSigEx
from charon_tpu.core.types import Duty, DutyType, PubKey
from charon_tpu.p2p import codec
from charon_tpu.p2p.adapters import TcpParSigTransport, TcpQbftNet
from charon_tpu.p2p.transport import P2PNode, PeerSpec
from charon_tpu.testutil.waiting import wait_until

CLUSTER_HASH = b"\x11" * 32


def free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


async def make_mesh(n):
    keys = [k1util.generate_private_key() for _ in range(n)]
    ports = free_ports(n)
    specs = [
        PeerSpec(
            index=i,
            pubkey=k1util.public_key_to_bytes(keys[i].public_key()),
            host="127.0.0.1",
            port=ports[i],
        )
        for i in range(n)
    ]
    nodes = [P2PNode(i, keys[i], specs, CLUSTER_HASH) for i in range(n)]
    for node in nodes:
        await node.start()
    return nodes


def test_codec_roundtrip():
    duty = Duty(7, DutyType.ATTESTER)
    psig = ParSignedData(
        data=SignedData("randao", 3, b"\x05" * 96), share_idx=2
    )
    msg = {"duty": duty, "set": {PubKey("0xab"): psig}}
    assert codec.decode(codec.encode(msg)) == msg
    qmsg = qbft.Msg(
        qbft.MsgType.PRE_PREPARE, duty, 1, 2, b"\x09" * 32,
        justification=(qbft.Msg(qbft.MsgType.ROUND_CHANGE, duty, 0, 2),),
    )
    assert codec.decode(codec.encode(qmsg)) == qmsg


def test_send_receive_and_ping():
    async def run():
        nodes = await make_mesh(3)
        try:
            got = []

            async def handler(from_idx, msg):
                got.append((from_idx, msg))
                return {"ok": True}

            nodes[1].register_handler("test", handler)
            resp = await nodes[0].send(1, "test", {"hello": 1}, await_response=True)
            assert resp == {"ok": True}
            assert got == [(0, {"hello": 1})]

            pong = await nodes[2].send(0, "ping", None, await_response=True)
            assert pong == {"pong": 0}
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_handshake_rejects_unknown_key():
    async def run():
        nodes = await make_mesh(2)
        try:
            # an imposter with a fresh key pretending to be node 1
            imposter_key = k1util.generate_private_key()
            specs = list(nodes[0].peers.values()) + [nodes[0].self_spec]
            imposter = P2PNode(1, imposter_key, specs, CLUSTER_HASH)
            with pytest.raises(Exception):
                await imposter.send(0, "ping", None, await_response=True)
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_qbft_over_tcp():
    async def run():
        nodes = await make_mesh(4)
        try:
            nets = [TcpQbftNet(node) for node in nodes]
            cons = [QBFTConsensus(nets[i], 4, round_timeout=0.5, timer="inc") for i in range(4)]
            decided = []

            for c in cons:

                async def sub(duty, val, _c=None):
                    decided.append(val)

                c.subscribe(sub)

            duty = Duty(9, DutyType.ATTESTER)
            sets = [{PubKey("0xaa"): f"value-{i}"} for i in range(4)]
            await asyncio.wait_for(
                asyncio.gather(
                    *(cons[i].propose(duty, sets[i]) for i in range(4))
                ),
                15,
            )
            assert len(decided) == 4
            assert len({repr(d) for d in decided}) == 1
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_parsigex_over_tcp():
    async def run():
        nodes = await make_mesh(3)
        try:
            transports = [TcpParSigTransport(node) for node in nodes]
            exes = [
                ParSigEx(i + 1, transports[i], verifier=None)
                for i in range(3)
            ]
            received = {i: [] for i in range(3)}
            for i, ex in enumerate(exes):

                async def sub(duty, sset, _i=i):
                    received[_i].append((duty, sset))

                ex.subscribe(sub)

            duty = Duty(5, DutyType.ATTESTER)
            psig = ParSignedData(
                data=SignedData("randao", 0, b"\x07" * 96), share_idx=1
            )
            await exes[0].broadcast(duty, {PubKey("0xbb"): psig})
            await wait_until(
                lambda: received[1] and received[2],
                "the broadcast at both peers",
            )
            assert not received[0]
            assert received[1][0][1][PubKey("0xbb")] == psig
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_inbound_spans_do_not_inherit_the_span_that_dialed_the_connection():
    """A connection outlives the call that opened it. The span active at
    a node's FIRST broadcast (one duty's propose edge) must not become the
    ambient parent of what later arrives on that connection: a later
    duty's receive span belongs to its own duty's trace (trace context
    crosses the wire in the frame's tctx only)."""
    from charon_tpu.app import tracer

    async def run():
        nodes = await make_mesh(2)
        try:
            t0 = tracer.Tracer()
            transports = [TcpParSigTransport(node) for node in nodes]
            exes = [
                ParSigEx(1, transports[0], verifier=None, tracer=t0),
                ParSigEx(2, transports[1], verifier=None),
            ]
            first, later = Duty(5, DutyType.ATTESTER), Duty(6, DutyType.ATTESTER)

            def psig(share):
                return ParSignedData(
                    data=SignedData("randao", 0, b"\x07" * 96), share_idx=share
                )

            with tracer.span("consensus.propose", duty=first, tracer=t0):
                # dials node 1 inside duty 5's span
                await exes[0].broadcast(first, {PubKey("0xbb"): psig(1)})
            await asyncio.sleep(0.2)
            # node 1 answers on whatever connection the pair has, from no span
            await exes[1].broadcast(later, {PubKey("0xbb"): psig(2)})
            await wait_until(
                lambda: [s for s in t0.spans if s.name == "parsigex.receive"],
                "node 0's parsigex.receive span",
            )
            (recv,) = [s for s in t0.spans if s.name == "parsigex.receive"]
            assert recv.attrs["duty"] == str(later)
            assert recv.trace_id == tracer.duty_trace_id(later)
            assert recv.parent_id == ""
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())
