"""Wire-path overhaul (ISSUE 7): binary codec round-trips, decode
strictness (typed CodecError for every malformation), chaos-corruption
fuzzing, envelope version sniffing, and binary <-> JSON transport
interop (mixed-version cluster) with trace context riding binary
frames.

The TCP-level tests need the `cryptography` package (k1 identity +
AEAD framing) and skip cleanly without it; the codec-level tests run
anywhere.
"""

from __future__ import annotations

import json
import random

import pytest

from charon_tpu.core import qbft
from charon_tpu.core.eth2data import (
    Attestation,
    AttestationData,
    AttestationDuty,
    Checkpoint,
    ParSignedData,
    SignedData,
    SyncCommitteeContribution,
    SyncSelectionData,
)
from charon_tpu.core.types import Duty, DutyType, PubKey
from charon_tpu.p2p import codec
from charon_tpu.testutil.waiting import wait_until

DUTY = Duty(123456, DutyType.ATTESTER)
ATT = Attestation(
    aggregation_bits=tuple(bool(i % 3) for i in range(64)),
    data=AttestationData(
        slot=123456,
        index=3,
        beacon_block_root=b"\x11" * 32,
        source=Checkpoint(3858, b"\x22" * 32),
        target=Checkpoint(3859, b"\x33" * 32),
    ),
    signature=b"\x44" * 96,
)


def _parsig_set(n=3, payload=ATT, kind="attestation"):
    return {
        PubKey("0x" + (bytes([i + 1]) * 48).hex()): ParSignedData(
            data=SignedData(kind, payload, bytes([i + 1]) * 96),
            share_idx=i + 1,
        )
        for i in range(n)
    }


# -- binary round-trips ------------------------------------------------------


@pytest.mark.parametrize(
    "value",
    [
        None,
        True,
        False,
        0,
        1,
        -1,
        123456,
        -(2**70),
        2**300,
        1.5,
        "",
        "tctx-" + "ab" * 16,
        b"",
        b"\x00" * 96,
        (),
        (1, "two", b"\x03", None),
        tuple(bool(i % 2) for i in range(77)),  # bitmap path, odd tail
        {"a": 1, b"k": (True, False)},
        DutyType.ATTESTER,
        qbft.MsgType.ROUND_CHANGE,
        DUTY,
        ATT,
        AttestationDuty(ATT.data, 64, 3, 7),
        SyncSelectionData(5, 2),
        SyncCommitteeContribution(5, b"\x01" * 32, 2),
    ],
)
def test_binary_roundtrip_values(value):
    assert codec.decode_binary(codec.encode_binary(value)) == value


def test_binary_roundtrip_hot_frames():
    sset = _parsig_set()
    frame = {"duty": DUTY, "set": sset, "tctx": "ab" * 16 + "-" + "cd" * 8}
    assert codec.decode_binary(codec.encode_binary(frame)) == frame
    qmsg = qbft.Msg(
        qbft.MsgType.PRE_PREPARE,
        DUTY,
        1,
        2,
        b"\x09" * 32,
        justification=(
            qbft.Msg(qbft.MsgType.ROUND_CHANGE, DUTY, 0, 2, prepared_round=1),
        ),
        signature=b"\x0a" * 64,
    )
    assert codec.decode_binary(codec.encode_binary(qmsg)) == qmsg


def test_binary_matches_json_semantics():
    """Both codecs must decode to IDENTICAL objects (lists->tuples,
    enum identity, bytes) — the transport sniffs per frame, so a mixed
    cluster sees both representations of the same message."""
    frame = {"duty": DUTY, "set": _parsig_set(), "tctx": None}
    assert codec.decode_binary(codec.encode_binary(frame)) == codec.decode(
        codec.encode(frame)
    )


def test_binary_cold_type_json_fallback():
    """Spec containers have no wire id: they ride an embedded-JSON tag
    inside the binary stream (Proposal values during proposer
    consensus)."""
    from charon_tpu.eth2util import spec

    e1d = spec.Eth1Data(b"\x01" * 32, 5, b"\x02" * 32)
    wire = codec.encode_binary(e1d)
    assert codec.decode_binary(wire) == e1d
    # and nested inside a hot container
    sd = SignedData("block", e1d, b"\x03" * 96)
    assert codec.decode_binary(codec.encode_binary(sd)) == sd


def test_binary_smaller_than_json():
    frame = {"duty": DUTY, "set": _parsig_set(6), "tctx": "ab" * 16 + "-" + "cd" * 8}
    assert len(codec.encode_binary(frame)) < len(codec.encode(frame)) / 2


def test_binary_omitted_defaulted_fields_fill():
    """A binary frame carrying fewer fields than we know (older minor)
    fills the trailing defaulted fields, and one missing a REQUIRED
    field is rejected — protonil parity with the JSON codec."""
    sd = SignedData("attestation", 5)  # signature defaults to b""
    assert codec.decode_binary(codec.encode_binary(sd)) == sd

    # hand-build a SignedData frame with only 2 of 3 fields
    wire = bytearray(codec.encode_binary(sd))
    # tag, wire_id, nfields — truncate the field count and the payload
    assert wire[0] == 0x0A
    full = codec.decode_binary(bytes(wire))
    assert full.signature == b""

    # required field missing -> CodecError naming the field
    duty_wire = bytearray(codec.encode_binary(DUTY))
    duty_wire[2] = 1  # claim 1 field (slot only; type is required)
    # strip the encoded enum value bytes so the frame stays consistent
    # (slot zigzag varint follows the header)
    # find end of the first field: tag + varint
    pos = 3
    assert duty_wire[pos] == 0x03
    pos += 1
    while duty_wire[pos] & 0x80:
        pos += 1
    pos += 1
    with pytest.raises(codec.CodecError, match="missing fields.*type"):
        codec.decode_binary(bytes(duty_wire[:pos]))


def test_binary_unknown_trailing_fields_dropped():
    """A newer minor may append fields: extras are self-describing and
    dropped (cross-minor window parity)."""
    wire = bytearray(codec.encode_binary(DUTY))
    assert wire[2] == 2  # Duty has 2 fields
    wire[2] = 3
    wire += codec.encode_binary("future-field")
    assert codec.decode_binary(bytes(wire)) == DUTY


# -- decode strictness (satellite): typed CodecError everywhere --------------


def test_json_malformed_hex_is_codec_error():
    wire = json.dumps({"__b": "zz-not-hex"}).encode()
    with pytest.raises(codec.CodecError):
        codec.decode(wire)


def test_json_unknown_enum_is_codec_error():
    wire = json.dumps({"__e": "NoSuchEnum", "v": 1}).encode()
    with pytest.raises(codec.CodecError):
        codec.decode(wire)
    wire = json.dumps({"__e": "DutyType", "v": "not-a-value"}).encode()
    with pytest.raises(codec.CodecError):
        codec.decode(wire)


@pytest.mark.parametrize(
    "payload",
    [
        {"__l": 42},
        {"__l": "abc"},
        {"__l": {"x": 1}},
        {"__d": 42},
        {"__d": "abc"},
        {"__d": [[1, 2, 3]]},
        {"__d": [1, 2]},
    ],
)
def test_json_non_list_container_payloads_are_codec_errors(payload):
    with pytest.raises(codec.CodecError):
        codec.decode(json.dumps(payload).encode())


def test_json_unknown_type_and_garbage_are_codec_errors():
    with pytest.raises(codec.CodecError):
        codec.decode(json.dumps({"__t": "NoSuchType"}).encode())
    with pytest.raises(codec.CodecError):
        codec.decode(b"not json at all")
    with pytest.raises(codec.CodecError):
        codec.decode(b"\xff\xfe binary garbage")
    # CodecError still satisfies pre-existing ValueError handlers
    assert issubclass(codec.CodecError, ValueError)


def test_binary_truncation_and_garbage_are_codec_errors():
    wire = codec.encode_binary({"duty": DUTY, "set": _parsig_set(2)})
    for cut in (0, 1, 2, len(wire) // 2, len(wire) - 1):
        with pytest.raises(codec.CodecError):
            codec.decode_binary(wire[:cut])
    with pytest.raises(codec.CodecError):
        codec.decode_binary(wire + b"\x00")  # trailing bytes
    with pytest.raises(codec.CodecError):
        codec.decode_binary(bytes([0x7F]) + wire)  # unknown tag
    with pytest.raises(codec.CodecError):
        codec.decode_binary(bytes([0x0A, 0x7F, 0x00]))  # unknown wire id


def test_codec_fuzz_corrupted_frames_never_raise_untyped():
    """Chaos-corruption fuzz: random mutations of valid wire bytes
    (both codecs) must either decode to SOMETHING or raise CodecError —
    never a bare KeyError/TypeError/struct.error that would have
    escaped the transport's typed per-frame drop."""
    rng = random.Random(1234)
    frames = [
        codec.encode_binary({"duty": DUTY, "set": _parsig_set(2)}),
        codec.encode_binary(
            qbft.Msg(qbft.MsgType.PREPARE, DUTY, 1, 2, b"\x09" * 32)
        ),
        codec.encode({"duty": DUTY, "set": _parsig_set(2)}),
    ]
    for _ in range(600):
        wire = bytearray(rng.choice(frames))
        for _ in range(rng.randint(1, 6)):
            op = rng.random()
            if op < 0.4 and wire:
                wire[rng.randrange(len(wire))] = rng.randrange(256)
            elif op < 0.7 and wire:
                del wire[rng.randrange(len(wire))]
            else:
                wire.insert(rng.randrange(len(wire) + 1), rng.randrange(256))
        try:
            codec.decode_binary(bytes(wire))
        except codec.CodecError:
            pass
        try:
            codec.decode(bytes(wire))
        except codec.CodecError:
            pass


def test_envelope_roundtrip_and_version_sniff():
    msg = {"duty": DUTY, "set": _parsig_set(2), "tctx": "ab" * 16 + "-" + "cd" * 8}
    for binary in (True, False):
        wire = codec.encode_envelope("parsigex/2.0.0", "rid1", "req", msg, binary)
        env = codec.decode_envelope(wire)
        assert env["p"] == "parsigex/2.0.0"
        assert env["id"] == "rid1"
        assert env["k"] == "req"
        assert env["d"] == msg
        # trace context survives the frame byte-for-byte
        assert env["d"]["tctx"] == "ab" * 16 + "-" + "cd" * 8
    assert codec.encode_envelope("p", "i", "req", msg, True)[0] == codec.BINARY_V1
    assert codec.encode_envelope("p", "i", "req", msg, False)[0:1] == b"{"
    # unknown version byte -> typed error, not a crash
    with pytest.raises(codec.CodecError):
        codec.decode_envelope(b"\x02rest")
    with pytest.raises(codec.CodecError):
        codec.decode_envelope(b"")
    # rsp kind + empty payload
    env = codec.decode_envelope(codec.encode_envelope("p", "i", "rsp", None, True))
    assert env["k"] == "rsp" and env["d"] is None


def test_envelope_tolerates_missing_request_id():
    """A JSON envelope without an id (fire-and-forget frames may omit
    it) decodes to id=None, and re-encoding a response for it on the
    binary path must not crash (regression: recv loop died on
    None.encode())."""
    wire = json.dumps({"p": "ping", "k": "req"}).encode()
    env = codec.decode_envelope(wire)
    assert env["id"] is None
    out = codec.encode_envelope(env["p"], env["id"], "rsp", {"pong": 1}, True)
    back = codec.decode_envelope(out)
    assert back["id"] == "" and back["d"] == {"pong": 1}


def test_int_beyond_wire_limit_fails_at_encode():
    """Ints past the decoders' 1024-bit varint cap must fail loudly at
    the SENDER, not as a silent drop on every receiver."""
    big = 1 << 1100
    with pytest.raises(TypeError):
        codec.encode_binary(big)
    # the largest spec int class (uint256) stays comfortably inside
    assert codec.decode_binary(codec.encode_binary(2**256 - 1)) == 2**256 - 1


def test_transport_import_tolerates_only_missing_cryptography():
    """The p2p package guard masks ONLY the optional `cryptography`
    dependency; the codec surface is importable regardless."""
    import charon_tpu.p2p as p2p

    assert p2p.CodecError is codec.CodecError
    try:
        import cryptography  # noqa: F401

        assert p2p.P2PNode is not None
    except ModuleNotFoundError:
        assert p2p.P2PNode is None


def test_envelope_fuzz_never_raises_untyped():
    rng = random.Random(99)
    msg = {"duty": DUTY, "set": _parsig_set(2), "tctx": None}
    frames = [
        bytes(codec.encode_envelope("parsigex/2.0.0", "r", "req", msg, True)),
        bytes(codec.encode_envelope("parsigex/2.0.0", "r", "req", msg, False)),
    ]
    for _ in range(400):
        wire = bytearray(rng.choice(frames))
        for _ in range(rng.randint(1, 5)):
            if rng.random() < 0.5 and wire:
                wire[rng.randrange(len(wire))] = rng.randrange(256)
            elif wire:
                del wire[rng.randrange(len(wire))]
        try:
            codec.decode_envelope(bytes(wire))
        except codec.CodecError:
            pass


# -- transport interop (TCP mesh; needs `cryptography`) ----------------------


def _make_mesh_mixed():
    """3-node localhost mesh: nodes 0 and 1 speak binary, node 2 is
    pinned to wire version 0 (a JSON-only older minor)."""
    import socket

    from charon_tpu.app import k1util
    from charon_tpu.p2p.transport import P2PNode, PeerSpec

    keys = [k1util.generate_private_key() for _ in range(3)]
    socks, ports = [], []
    for _ in range(3):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    specs = [
        PeerSpec(
            index=i,
            pubkey=k1util.public_key_to_bytes(keys[i].public_key()),
            host="127.0.0.1",
            port=ports[i],
        )
        for i in range(3)
    ]
    nodes = [
        P2PNode(i, keys[i], specs, b"\x11" * 32,
                wire_version=(0 if i == 2 else 1))
        for i in range(3)
    ]
    return nodes


def test_binary_json_transport_interop():
    """A binary-speaking node interops with a JSON-speaking node: the
    same ParSigEx payload flows both directions on every edge of a
    mixed-version mesh, and binary peers actually negotiated binary."""
    pytest.importorskip("cryptography")
    import asyncio

    async def run():
        nodes = _make_mesh_mixed()
        for node in nodes:
            await node.start()
        try:
            got = {i: [] for i in range(3)}
            for i, node in enumerate(nodes):

                async def handler(from_idx, msg, _i=i):
                    got[_i].append((from_idx, msg))
                    return {"ok": _i}

                node.register_handler("test", handler)
            payload = {"duty": DUTY, "set": _parsig_set(2),
                       "tctx": "ab" * 16 + "-" + "cd" * 8}
            # every directed edge: binary->binary, binary->json, json->binary
            for src in range(3):
                for dst in range(3):
                    if src == dst:
                        continue
                    resp = await nodes[src].send(
                        dst, "test", payload, await_response=True
                    )
                    assert resp == {"ok": dst}
            for i in range(3):
                assert len(got[i]) == 2
                for _from, msg in got[i]:
                    assert msg == payload
                    assert msg["tctx"] == "ab" * 16 + "-" + "cd" * 8
            # wire negotiation: 0<->1 binary, anything with 2 is JSON
            assert nodes[0]._conns[1].wire == 1
            assert nodes[0]._conns[2].wire == 0
            assert nodes[2]._conns[0].wire == 0
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_broadcast_single_encode_and_codec_error_drop():
    """Broadcast encodes once per codec (cache hit still counts bytes),
    and a malformed binary frame on a live connection is dropped +
    counted without killing the connection."""
    pytest.importorskip("cryptography")
    import asyncio

    from charon_tpu.p2p import transport as tmod

    async def run():
        nodes = _make_mesh_mixed()
        for node in nodes:
            await node.start()
        observed = []
        nodes[0].wire_observer = lambda *a: observed.append(a)
        try:
            seen = []

            async def handler(from_idx, msg):
                seen.append((from_idx, msg))
                return None

            for node in nodes[1:]:
                node.register_handler("bcast", handler)
            payload = {"duty": DUTY, "set": _parsig_set(2), "tctx": None}
            await nodes[0].broadcast("bcast", payload)
            await wait_until(
                lambda: len(seen) >= 2,
                "the broadcast at both peers",
            )
            assert len(seen) == 2
            # one timed binary encode + one timed JSON encode (node 2);
            # no third encode — the binary body was cached per codec
            timed = [o for o in observed if o[0] == "tx" and o[3] is not None]
            assert sorted(o[1] for o in timed) == ["binary", "json"]

            # now a malformed binary frame on the live 0->1 connection:
            # dropped + counted, connection stays usable
            conn = nodes[0]._conns[1]
            before = nodes[1].codec_dropped
            async with conn.lock:
                tmod._write_sframe(conn, bytes([1, 0x7F, 0xFF, 0xFF]))
                await conn.writer.drain()
            await wait_until(
                lambda: nodes[1].codec_dropped > before,
                "the malformed frame's drop",
            )
            assert nodes[1].codec_dropped == before + 1
            pong = await nodes[0].send(1, "ping", None, await_response=True)
            assert pong == {"pong": 1}
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_chaos_garbage_never_kills_transport_codec():
    """testutil/chaos-style garbage blasts decode to CodecError at the
    codec layer for EVERY seeded frame — the invariant the transport's
    per-frame drop depends on."""
    rng = random.Random(7)
    for _ in range(300):
        blob = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 200)))
        try:
            codec.decode_envelope(blob)
        except codec.CodecError:
            pass
        try:
            codec.decode_binary(blob)
        except codec.CodecError:
            pass


def test_peer_codec_quarantine_exponential_backoff(monkeypatch):
    """Repeated CodecError frames from ONE peer inside the strike
    window impose a temporary mute (frames drop before decode), a
    repeat offense doubles the mute, and a clean frame after expiry
    forgives the backoff level (ISSUE 8 satellite)."""
    pytest.importorskip("cryptography")
    import asyncio

    from charon_tpu.p2p import transport as tmod

    # a mute of 1 s (then 2 s): the valid frame below must ARRIVE
    # inside it, also where a loaded worker's event loop stalls for
    # some tenths of a second
    monkeypatch.setattr(tmod, "QUARANTINE_STRIKES", 3)
    monkeypatch.setattr(tmod, "QUARANTINE_BASE", 1.0)
    monkeypatch.setattr(tmod, "RECV_TIMEOUT", 0.5)

    async def blast_malformed(src, dst_idx, n):
        # through the transport's own look-up: a send that timed out
        # has dropped its connection from the table (P2PNode.send,
        # "drop the dead connection"), and the next frame re-dials
        conn = await src._get_conn(dst_idx)
        async with conn.lock:
            for _ in range(n):
                tmod._write_sframe(conn, bytes([1, 0x7F, 0xFF, 0xFF]))
            await conn.writer.drain()

    async def run():
        nodes = _make_mesh_mixed()
        for node in nodes:
            await node.start()
        mutes = []
        nodes[1].quarantine_observer = lambda p, m: mutes.append((p, m))

        try:
            assert await nodes[0].send(1, "ping", None, await_response=True)
            # strikes 1..3 inside the window: mute imposed at base
            await blast_malformed(nodes[0], 1, 3)
            await wait_until(lambda: nodes[1].peer_quarantines == 1, "the first mute")
            assert nodes[1].peer_quarantined(0)
            assert mutes == [(0, 1.0)]
            # while muted, even a VALID frame drops before decode
            dropped_before = nodes[1].quarantined_frames
            with pytest.raises(asyncio.TimeoutError):
                await nodes[0].send(1, "ping", None, await_response=True)
            assert nodes[1].quarantined_frames > dropped_before
            # repeat offense right after expiry: the mute DOUBLES
            await wait_until(lambda: not nodes[1].peer_quarantined(0), "the mute's end")
            await blast_malformed(nodes[0], 1, 3)
            await wait_until(lambda: nodes[1].peer_quarantines == 2, "the second mute")
            assert mutes == [(0, 1.0), (0, 2.0)]
            # a clean frame after expiry forgives the backoff level
            await wait_until(lambda: not nodes[1].peer_quarantined(0), "the mute's end")
            assert await nodes[0].send(1, "ping", None, await_response=True)
            assert not nodes[1]._quarantine._level
            # next offense starts back at the base mute
            await blast_malformed(nodes[0], 1, 3)
            await wait_until(lambda: nodes[1].peer_quarantines == 3, "the third mute")
            assert mutes[-1] == (0, 1.0)
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_peer_quarantine_state_machine_fake_clock():
    """The quarantine state machine itself (p2p/quarantine.py), driven
    on a fake clock: strike-window expiry, exponential backoff across
    repeat offenses capped at max_mute, and forgiveness — the
    cryptography-free half every environment exercises."""
    from charon_tpu.p2p.quarantine import PeerQuarantine

    now = [0.0]
    mutes = []
    q = PeerQuarantine(
        strikes=3, window=10.0, base=2.0, max_mute=6.0,
        observer=lambda p, m: mutes.append((p, m)), clock=lambda: now[0],
    )
    # two strikes then the window expires: no mute
    assert q.strike(7) is None and q.strike(7) is None
    now[0] += 11.0
    assert q.strike(7) is None and not q.muted(7)
    # three inside the window: base mute
    assert q.strike(7) is None and q.strike(7) == 2.0
    assert q.muted(7) and q.quarantines == 1
    # other peers are unaffected
    assert not q.muted(8)
    # repeat offenses double, capped at max_mute
    now[0] += 2.5
    assert not q.muted(7)
    for _ in range(2):
        q.strike(7)
    assert q.strike(7) == 4.0
    now[0] += 4.5
    for _ in range(2):
        q.strike(7)
    assert q.strike(7) == 6.0  # 8.0 capped at max_mute
    # forgiveness resets the backoff level
    now[0] += 6.5
    q.forgive(7)
    for _ in range(2):
        q.strike(7)
    assert q.strike(7) == 2.0
    assert mutes == [(7, 2.0), (7, 4.0), (7, 6.0), (7, 2.0)]
