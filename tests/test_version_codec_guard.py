"""Version negotiation (ref: app/version) + wire nil-field guard
(ref: app/protonil) + peerinfo compatibility surfacing.
"""

from __future__ import annotations

import json

import pytest

from charon_tpu.app import version
from charon_tpu.p2p import codec
from charon_tpu.testutil.waiting import wait_until


def test_version_window():
    assert version.check_compatible(version.VERSION)
    assert version.check_compatible("0.1.9")
    assert not version.check_compatible("0.0.1")
    assert not version.check_compatible("9.9.9")
    assert version.minor("1.2.3") == "1.2"


def test_codec_roundtrip_still_works():
    from charon_tpu.core.types import Duty, DutyType

    duty = Duty(slot=5, type=DutyType.ATTESTER)
    assert codec.decode(codec.encode(duty)) == duty


def test_codec_rejects_missing_fields():
    """A peer omitting required fields must be rejected, not silently
    defaulted (ref: app/protonil nil-field guard)."""
    from charon_tpu.core.types import Duty, DutyType

    wire = json.loads(codec.encode(Duty(slot=5, type=DutyType.ATTESTER)))
    del wire["slot"]
    with pytest.raises(ValueError, match="missing fields.*slot"):
        codec.decode(json.dumps(wire).encode())


def test_codec_required_vs_defaulted_fields():
    from charon_tpu.core.eth2data import SignedData

    wire = json.loads(codec.encode(SignedData("attestation", "x", b"\x01")))
    # `signature` declares a default -> omissible (schema-evolution
    # window); `kind` does not -> required
    defaulted = dict(wire)
    del defaulted["signature"]
    decoded = codec.decode(json.dumps(defaulted).encode())
    assert decoded.signature == b""

    required = dict(wire)
    del required["kind"]
    with pytest.raises(ValueError, match="missing fields.*kind"):
        codec.decode(json.dumps(required).encode())


def test_bad_frame_does_not_kill_connection():
    """A malformed payload on a live conn drops the frame, not the
    connection (the reference survives bad protobufs the same way)."""
    import asyncio

    pytest.importorskip("cryptography")
    from charon_tpu.app import k1util
    from charon_tpu.p2p.transport import P2PNode, PeerSpec

    async def run():
        keys = [k1util.generate_private_key() for _ in range(2)]
        pubs = [k1util.public_key_to_bytes(k.public_key()) for k in keys]
        import socket

        socks = []
        ports = []
        for _ in range(2):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            socks.append(s)
        for s in socks:
            s.close()
        specs = [
            PeerSpec(index=i, pubkey=pubs[i], host="127.0.0.1", port=ports[i])
            for i in range(2)
        ]
        cluster_hash = b"\x09" * 32
        nodes = [
            P2PNode(i, keys[i], specs, cluster_hash) for i in range(2)
        ]
        for node in nodes:
            await node.start()
        try:
            got = []

            async def handler(idx, msg):
                if msg.get("boom"):
                    raise ValueError("handler exploded")
                got.append((idx, msg))

            nodes[1].register_handler("t/1", handler)
            # a frame whose handler raises must not tear down the conn
            await nodes[0].send(1, "t/1", {"boom": 1}, await_response=False)
            await asyncio.sleep(0.2)
            await nodes[0].send(1, "t/1", {"ok": 1}, await_response=False)
            await wait_until(
                lambda: any(msg == {"ok": 1} for _, msg in got),
                "the frame behind the one whose handler raised",
            )
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_peerinfo_flags_incompatible_peer():
    import asyncio

    from charon_tpu.app.peerinfo import PeerInfoService

    class FakeNode:
        peers = ()

        def register_handler(self, proto, h):
            self.handler = h

    async def run():
        node = FakeNode()
        svc = PeerInfoService(node, version.VERSION)
        await node.handler(
            2, {"version": "0.0.1", "start_time": 0.0, "now": 0.0}
        )
        await node.handler(
            3,
            {"version": version.VERSION, "start_time": 0.0, "now": 0.0},
        )
        assert svc.incompatible_peers() == [2]
        assert svc.peers[3].compatible

    asyncio.run(run())
