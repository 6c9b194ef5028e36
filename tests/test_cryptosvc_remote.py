"""Fake-clock unit battery for the remote crypto-plane client
(core/cryptosvc_client) and its wire frames (ISSUE 17 satellites).

Everything here is jax-free and cryptography-free: a stub service and
stub local plane stand in for the real coalescer stack, so the suite
pins the CLIENT's failure semantics — reconnect backoff schedule,
monotonic-clock heartbeat expiry (the PR 8 `_arm` wall/mono bug class
must not recur), relative-deadline propagation, half-open probe
single-flight, typed window sheds, and the server-address quarantine
exemption — without a device or a real tenant in sight.
"""

import asyncio
import random
import time

import pytest

from charon_tpu.app.expbackoff import Config, backoff_delay
from charon_tpu.core.cryptosvc import PlaneOverloadError
from charon_tpu.core.cryptosvc_client import RemotePlane
from charon_tpu.core.cryptosvc_server import CryptoServiceServer
from charon_tpu.core.cryptosvc_wire import (
    PROTOCOL,
    CryptoHeartbeat,
    CryptoResult,
    CryptoShed,
    CryptoSubmit,
    auth_proof,
    proof_ok,
)
from charon_tpu.p2p.codec import (
    CodecError,
    decode_envelope,
    encode_envelope,
)
from charon_tpu.p2p.quarantine import PeerQuarantine
from charon_tpu.tbls import TblsError
from charon_tpu.testutil.chaos import SkewedClock
from charon_tpu.testutil.waiting import wait_until

SEED = 20260808

TOKEN = "unit-token"
TENANT = "t1"


class FakeLocal:
    """Local-ladder stand-in: records every failover landing on it."""

    t = 3

    def __init__(self):
        self.verifies = []
        self.recombines = []

    async def verify(self, items, deadline=None):
        self.verifies.append((list(items), deadline))
        return [True] * len(items)

    async def recombine(
        self, pubshares, roots, partials, group_pks, indices,
        deadline=None,
    ):
        self.recombines.append((len(roots), deadline))
        return [b"sig"] * len(roots), [True] * len(roots)


class FakeSvc:
    """CryptoPlaneService stand-in for the real server: records
    submits, optionally delays or raises per-kind."""

    t = 3
    coalescer = None

    def __init__(self, delay=0.0, raises=None):
        self.submits = []
        self.delay = delay
        self.raises = raises

    async def submit(self, tenant_id, kind, args, lanes, deadline):
        self.submits.append((tenant_id, kind, args, lanes, deadline))
        if self.delay:
            await asyncio.sleep(self.delay)
        if self.raises is not None:
            raise self.raises
        if kind == "verify":
            return [True] * lanes
        return [b"sig"] * lanes, [True] * lanes


async def _connected_client(svc, server_kw=None, **kw):
    """A served FakeSvc plus a client that finished its handshake."""
    server = CryptoServiceServer(
        svc, {TENANT: TOKEN}, port=0, **(server_kw or {})
    )
    await server.start()
    client = RemotePlane(
        "127.0.0.1", server.port, TENANT, TOKEN,
        local=kw.pop("local", FakeLocal()), **kw,
    )
    await client.start()
    await wait_until(lambda: client.state != "down", "the client's handshake")
    assert client.state == "probing"
    return server, client


# -- reconnect backoff schedule ----------------------------------------------


def test_reconnect_backoff_matches_seeded_schedule():
    """Connect-refused retries follow exactly the pure
    expbackoff.backoff_delay schedule under the injected rng — the
    supervisor adds no hidden jitter or resets."""

    async def run():
        cfg = Config(
            base_delay=0.005, multiplier=2.0, jitter=0.2,
            max_delay=0.02,
        )
        # grab a port with nothing listening: bind-then-close
        srv = await asyncio.start_server(
            lambda r, w: None, "127.0.0.1", 0
        )
        port = srv.sockets[0].getsockname()[1]
        srv.close()
        await srv.wait_closed()
        client = RemotePlane(
            "127.0.0.1", port, TENANT, TOKEN, local=FakeLocal(),
            backoff_config=cfg, rng=random.Random(SEED),
        )
        await client.start()
        await wait_until(
            lambda: len(client.reconnect_delays) >= 5, "five reconnect attempts"
        )
        await client.close()
        got = client.reconnect_delays[:5]
        ref = random.Random(SEED)
        want = [backoff_delay(cfg, i, ref) for i in range(5)]
        assert got == want
        assert client.connects == 0 and client.state == "down"

    asyncio.run(run())


# -- heartbeat expiry: monotonic clock ONLY ----------------------------------


def test_heartbeat_expiry_pinned_to_injected_monotonic_clock():
    state = [100.0]
    client = RemotePlane(
        "127.0.0.1", 1, TENANT, TOKEN, local=FakeLocal(),
        heartbeat_timeout=3.0, clock=lambda: state[0],
    )
    assert not client._heartbeat_expired()
    state[0] += 3.0  # exactly at the bound: not yet expired
    assert not client._heartbeat_expired()
    state[0] += 0.1
    assert client._heartbeat_expired()


def test_wall_clock_jump_does_not_expire_heartbeat():
    """The PR 8 `_arm` bug class: a wall-clock step (NTP slew, skewed
    host) must neither fire nor mask heartbeat-miss detection. The
    default clock is time.monotonic, which SkewedClock (wall-only by
    design) cannot touch."""
    client = RemotePlane(
        "127.0.0.1", 1, TENANT, TOKEN, local=FakeLocal(),
        heartbeat_timeout=3.0,
    )
    with SkewedClock() as clk:
        clk.step(3600.0)  # one hour of wall skew
        assert not client._heartbeat_expired()


def test_heartbeat_echo_refreshes_last_pong():
    async def run():
        state = [50.0]
        svc = FakeSvc()
        server, client = await _connected_client(
            svc, clock=lambda: state[0], heartbeat_timeout=3.0,
            server_kw={"heartbeat": 0.05},
        )
        try:
            state[0] += 2.9
            # a round trip (probe) delivers result frames — but only
            # heartbeat ECHOES refresh the pong clock, so stay expired-
            # adjacent until the next echo arrives
            await client.verify([b"a", b"b"])
            await wait_until(
                lambda: client._last_pong >= state[0],
                "the pong of the newest heartbeat",
            )
            assert client._last_pong == state[0]
            assert not client._heartbeat_expired()
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


# -- deadline propagation ----------------------------------------------------


def test_deadline_rides_the_wire_as_relative_remainder():
    """The client ships `deadline - now` and the server rebases onto
    its own wall clock: captured absolute deadlines agree to within
    the round-trip slop, with no cross-host clock agreement assumed."""

    async def run():
        svc = FakeSvc()
        server, client = await _connected_client(svc)
        try:
            deadline = time.time() + 2.0
            res = await client.verify([b"a", b"b", b"c"], deadline)
            assert res == [True, True, True]
            (_, kind, _, lanes, got_deadline), = svc.submits
            assert kind == "verify" and lanes == 3
            assert got_deadline is not None
            assert abs(got_deadline - deadline) < 0.5
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


def test_no_deadline_ships_none():
    async def run():
        svc = FakeSvc()
        server, client = await _connected_client(svc)
        try:
            await client.verify([b"a"])
            assert svc.submits[0][4] is None
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


def test_expired_deadline_fails_over_before_request_timeout():
    """A remote that sits on the job past the duty deadline loses it to
    the local rung: the wait is bounded by the deadline remainder, not
    the (much longer) request timeout."""

    async def run():
        local = FakeLocal()
        svc = FakeSvc(delay=30.0)  # never answers in time
        server, client = await _connected_client(
            svc, local=local, request_timeout=60.0
        )
        try:
            t0 = time.monotonic()
            res = await client.verify([b"a"], time.time() + 0.2)
            took = time.monotonic() - t0
            assert res == [True]
            assert took < 2.0  # deadline-bounded, not 60 s
            assert client.failovers == {"timeout": 1}
            assert len(local.verifies) == 1
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


# -- half-open probe single-flight -------------------------------------------


def test_probe_single_flight_concurrent_jobs_run_local():
    """In "probing" exactly ONE job may try the remote; concurrent
    submissions degrade locally with reason "probing" instead of
    queueing behind an unproven connection."""

    async def run():
        local = FakeLocal()
        svc = FakeSvc(delay=0.1)
        server, client = await _connected_client(svc, local=local)
        try:
            results = await asyncio.gather(
                client.verify([b"a"]),
                client.verify([b"b"]),
                client.verify([b"c"]),
            )
            assert all(r == [True] for r in results)
            # one probe went remote, the rest rode the local ladder
            assert client.remote_jobs == 1
            assert client.failovers == {"probing": 2}
            assert len(local.verifies) == 2
            assert client.state == "up"
            # once up, everything goes remote again
            await client.verify([b"d"])
            assert client.remote_jobs == 2
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


def test_shed_settles_probe_too():
    """A typed shed proves the submit path as well as a result does:
    the connection goes "up" and the shed job degrades locally via the
    caller's PlaneOverloadError contract."""

    async def run():
        svc = FakeSvc(raises=PlaneOverloadError(TENANT, "jobs", "full"))
        server, client = await _connected_client(svc)
        try:
            res = await client.verify([b"a"])
            assert res == [True]  # failed over to the local rung
            assert client.state == "up"
            assert client.sheds == {"jobs": 1}
            assert client.failovers == {"shed": 1}
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


# -- typed local sheds on window overflow ------------------------------------


def test_inflight_window_overflow_sheds_typed():
    async def run():
        svc = FakeSvc()
        server, client = await _connected_client(
            svc, max_inflight_jobs=1, max_inflight_lanes=4
        )
        try:
            await client.verify([b"p"])  # probe settles -> "up"
            assert client.state == "up"
            svc.delay = 0.2
            first = asyncio.create_task(client.verify([b"a"]))
            await asyncio.sleep(0.05)  # first occupies the window
            assert client.inflight_jobs == 1
            with pytest.raises(PlaneOverloadError) as ei:
                await client.verify([b"b"])
            assert ei.value.reason == "jobs"
            assert ei.value.tenant == TENANT
            assert client.sheds == {}  # local shed, not a remote one
            assert await first == [True]
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


# -- tbls verdicts never fail over -------------------------------------------


def test_tbls_error_propagates_without_local_retry():
    async def run():
        local = FakeLocal()
        svc = FakeSvc()
        server, client = await _connected_client(svc, local=local)
        try:
            # probe first so the verdict job is a plain "up" round trip
            await client.verify([b"probe"])
            svc.raises = TblsError("bad share index")
            with pytest.raises(TblsError):
                await client.verify([b"a"])
            # the verdict is identical on every rung: NO local retry
            assert local.verifies == []
            assert client.failovers == {}
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


# -- quarantine: the configured server address never mutes -------------------


def test_quarantine_exempts_configured_server_address():
    """Satellite regression: a flapping/corrupting server must land in
    reconnect backoff, never in a codec mute that silently extends the
    outage. Fake clock; the same strikes DO mute a non-exempt peer."""
    state = [0.0]
    q = PeerQuarantine(
        strikes=3, window=10.0, base=5.0,
        clock=lambda: state[0], exempt={"10.0.0.1:9000"},
    )
    for _ in range(10):
        assert q.strike("10.0.0.1:9000") is None
        state[0] += 0.1
    assert not q.muted("10.0.0.1:9000")
    assert q.quarantines == 0
    # identical behavior from a non-exempt peer escalates
    mutes = [q.strike("10.0.0.2:9000") for _ in range(3)]
    assert mutes[:2] == [None, None] and mutes[2] == 5.0
    assert q.muted("10.0.0.2:9000")
    # the client constructs its own exemption from host:port
    client = RemotePlane(
        "10.9.8.7", 4242, TENANT, TOKEN, local=FakeLocal()
    )
    assert client.addr in client.quarantine.exempt


def test_client_codec_strike_recorded_but_never_escalates():
    async def run():
        svc = FakeSvc()
        server, client = await _connected_client(svc)
        try:
            for _ in range(20):
                client.quarantine.strike(client.addr)
            assert not client.quarantine.muted(client.addr)
        finally:
            await client.close()
            await server.close()

    asyncio.run(run())


# -- RPC frame strictness (satellite 2) --------------------------------------


def _envelope(msg) -> bytes:
    return encode_envelope(PROTOCOL, "", "req", msg, True)


@pytest.mark.parametrize(
    "msg",
    [
        CryptoSubmit(7, "verify", ((b"pk", b"root", b"sig"),), 1, 0.5),
        CryptoResult(7, value=(True, False), stats={"lanes": 2}),
        CryptoHeartbeat(3, echo=True),
        CryptoShed(9, "lanes", "window full"),
    ],
    ids=["submit", "result", "heartbeat", "shed"],
)
def test_rpc_frames_round_trip_binary(msg):
    env = decode_envelope(_envelope(msg))
    assert env["d"] == msg


def test_rpc_frames_reject_truncation():
    rng = random.Random(SEED)
    msg = CryptoSubmit(
        1, "verify", ((b"pk" * 24, b"r" * 32, b"s" * 48),), 1, 1.0
    )
    frame = _envelope(msg)
    for _ in range(32):
        cut = rng.randrange(1, len(frame))
        with pytest.raises(CodecError):
            decode_envelope(frame[:cut])


def test_rpc_frames_reject_trailing_garbage():
    rng = random.Random(SEED)
    frame = _envelope(CryptoResult(5, value=(True,)))
    for n in (1, 3, 17):
        tail = bytes(rng.randrange(256) for _ in range(n))
        with pytest.raises(CodecError):
            decode_envelope(frame + tail)


def test_rpc_frames_reject_unknown_wire_id():
    frame = bytearray(_envelope(CryptoHeartbeat(1)))
    # envelope: 0x01 | varint proto | varint req_id | kind | value;
    # the value starts with the registered type's single-byte wire id —
    # stomp it with an unassigned id and the decode must die typed
    idx = frame.index(0x1B)  # CryptoHeartbeat wire id 27
    frame[idx] = 0x7A  # unassigned, still < 0x80
    with pytest.raises(CodecError):
        decode_envelope(bytes(frame))


def test_auth_proof_is_keyed_and_nonce_bound():
    nonce = b"n" * 32
    proof = auth_proof(b"tok", nonce)
    assert proof_ok(b"tok", nonce, proof)
    assert not proof_ok(b"tok2", nonce, proof)
    assert not proof_ok(b"tok", b"m" * 32, proof)
    assert b"tok" not in proof  # the token never appears in the proof
