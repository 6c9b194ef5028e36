"""Aux p2p + app subsystems: relay forwarding, fuzz survival, privkeylock,
peerinfo exchange."""

import asyncio
import json

import pytest

# the node-identity stack (app/k1util, eth2util/keystore) needs the
# optional `cryptography` package; skip LOUDLY where absent instead
# of erroring at collection (ISSUE 17 satellite — no test deleted)
pytest.importorskip(
    "cryptography",
    reason="app.k1util requires the optional 'cryptography' package",
)

from charon_tpu.app.peerinfo import PeerInfoService
from charon_tpu.app.privkeylock import PrivKeyLock, PrivKeyLockError
from charon_tpu.testutil.chaos import blast_garbage, fuzz_node
from charon_tpu.p2p.relay import RelayClient, RelayServer
from charon_tpu.testutil.waiting import wait_until

from tests.test_p2p import make_mesh  # reuse mesh fixture helpers


def test_privkeylock(tmp_path):
    path = tmp_path / "lock"
    l1 = PrivKeyLock(path, "run")
    l1.acquire()
    l2 = PrivKeyLock(path, "run")
    with pytest.raises(PrivKeyLockError):
        l2.acquire()
    # stale lock is taken over
    data = json.loads(path.read_text())
    data["timestamp"] -= 60
    path.write_text(json.dumps(data))
    l2.acquire()


def test_relay_forwarding():
    async def run():
        relay = RelayServer()
        port = await relay.start()
        try:
            got = []
            c0 = RelayClient("127.0.0.1", port, b"\x01" * 32, 0)
            c1 = RelayClient("127.0.0.1", port, b"\x01" * 32, 1)
            c1.on_frame(lambda frm, data: got.append((frm, data)))
            await c0.connect()
            await c1.connect()
            await c0.send(1, b"hello-via-relay")
            await wait_until(
                lambda: got,
                "the relayed frame",
            )
            assert got == [(0, b"hello-via-relay")]
            # different cluster hash is isolated
            cx = RelayClient("127.0.0.1", port, b"\x02" * 32, 0)
            await cx.connect()
            await cx.send(1, b"cross-cluster")
            await asyncio.sleep(0.1)
            assert len(got) == 1
            await c0.close()
            await c1.close()
            await cx.close()
        finally:
            await relay.stop()

    asyncio.run(run())


def test_nodes_survive_fuzzing():
    async def run():
        nodes = await make_mesh(3)
        try:
            # raw garbage at the server: handshake must reject, node lives
            await blast_garbage(
                nodes[0].self_spec.host, nodes[0].self_spec.port, 20
            )
            await asyncio.sleep(0.1)

            # fuzzed sender: some messages lost/corrupted, node still works
            fuzz_node(nodes[1], rate=0.5)
            delivered = []

            async def handler(frm, msg):
                delivered.append(msg)
                return None

            nodes[0].register_handler("t", handler)
            for i in range(30):
                try:
                    await nodes[1].send(0, "t", {"i": i})
                except Exception:
                    pass
            await asyncio.sleep(0.2)
            # un-fuzzed peer still communicates with node 0 normally
            ok = await nodes[2].send(0, "ping", None, await_response=True)
            assert ok == {"pong": 0}
            assert delivered  # at least some made it through the chaos
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())


def test_peerinfo_exchange():
    async def run():
        nodes = await make_mesh(2)
        try:
            s0 = PeerInfoService(nodes[0], "v1.0")
            s1 = PeerInfoService(nodes[1], "v1.1")
            await s0.poll_once()
            assert s0.peers[1].version == "v1.1"
            assert abs(s0.peers[1].clock_offset) < 1.0
            # the polled peer also learned about us from the request
            assert s1.peers[0].version == "v1.0"
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(run())
