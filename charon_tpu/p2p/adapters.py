"""TCP-backed transports for the workflow components.

These adapt the P2PNode mesh to the transport interfaces the in-memory
simnet fakes implement, so the same ParSigEx / QBFTConsensus components run
over real sockets (ref: the reference's parsigex protocol
/charon/parsigex/2.0.0 — p2p/parsigex.go:23 — and consensus transport
core/consensus/qbft/transport.go).
"""

from __future__ import annotations

import asyncio

from charon_tpu.p2p.transport import P2PNode

PARSIGEX_PROTOCOL = "parsigex/2.0.0"
QBFT_PROTOCOL = "qbft/2.0.0"


class TcpParSigTransport:
    """Drop-in for core.parsigex.MemTransport over the TCP mesh.

    Node indices are 0-based; share indices 1-based (idx = share-1)."""

    def __init__(self, node: P2PNode) -> None:
        self.node = node
        self.local = None
        # the receive in flight per (peer index, duty type): _on_msg
        self._in_flight: dict[tuple, asyncio.Task] = {}
        node.register_handler(PARSIGEX_PROTOCOL, self._on_msg)

    def attach(self, parsigex) -> None:
        self.local = parsigex

    async def send(
        self, from_share_idx: int, duty, signed_set, tctx=None
    ) -> None:
        # trace context rides the frame so peer-node spans join the
        # sender's duty trace (ref: OTel ctx in the p2p envelopes)
        await self.node.broadcast(
            PARSIGEX_PROTOCOL, {"duty": duty, "set": signed_set, "tctx": tctx}
        )

    async def _on_msg(self, from_idx: int, msg):
        if self.local is None:
            return None
        # beside the connection, not in its read loop: receive() ends
        # when the set's verify flush does (a device program, seconds),
        # and the peer's NEXT set — another kind of duty due at the same
        # instant — must not wait behind it. Two peers that send their
        # two sets in opposite orders would otherwise hold each kind's
        # wave short of the other's set until a window timer broke the
        # circle (PERF.md, PR 39). ONE receive in flight per (peer, duty
        # type): a second set of a type waits here, in the read loop, for
        # the first to end, which is the backpressure the connection
        # always had — a peer holds at most a set of each type in the
        # decode pool and the coalescer, and its sets of a type are
        # received in the order it sent them.
        key = (from_idx, getattr(msg["duty"], "type", None))
        earlier = self._in_flight.get(key)
        if earlier is not None:
            await asyncio.wait({earlier})
        task = self.node.detach(self._receive(from_idx, msg))
        self._in_flight[key] = task
        task.add_done_callback(lambda t: self._landed(key, t))
        return None

    def _landed(self, key, task) -> None:
        if self._in_flight.get(key) is task:
            del self._in_flight[key]

    async def _receive(self, from_idx: int, msg) -> None:
        try:
            # channel identity: mesh node index -> 1-based share index,
            # so receive() can attribute spoofed/invalid sets to the
            # authenticated peer the frame arrived from
            await self.local.receive(
                msg["duty"],
                msg["set"],
                tctx=msg.get("tctx"),
                sender=from_idx + 1,
            )
        except Exception as e:  # noqa: BLE001 — a frame's fault drops that frame
            # what the read loop does with a handler's error: a malformed
            # payload is the peer's strike, anything else a logged drop
            self.node.drop_frame(from_idx, e)


class TcpQbftNet:
    """Drop-in for core.consensus_qbft.MemMsgNet over the TCP mesh."""

    def __init__(self, node: P2PNode) -> None:
        self.node = node
        self.local = None
        node.register_handler(QBFT_PROTOCOL, self._on_msg)

    def attach(self, consensus) -> int:
        self.local = consensus
        return self.node.index

    async def broadcast(
        self, from_idx: int, duty, msg, values, tctx=None
    ) -> None:
        await self.node.broadcast(
            QBFT_PROTOCOL,
            {"duty": duty, "msg": msg, "vals": values, "tctx": tctx},
        )

    async def _on_msg(self, from_idx: int, m):
        if self.local is not None:
            self.local.deliver(
                m["duty"],
                m["msg"],
                m["vals"],
                tctx=m.get("tctx"),
                sender=from_idx,
            )
        return None
