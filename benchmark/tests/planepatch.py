"""A patch for `run.Rehearsal` (as helpers.PATCHES are): the host-only
rehearsal node gets the crypto-plane SERVICE PATH — tenant service,
coalescer, flush pipeline, span bridge — over a plane that runs no
program, so the spans and per-flush stats of that path exist on the CPU.

The plane decides nothing a test may lean on: a verify lane that decodes
passes (as testutil/simnet.SimHostPlane), and a row is recombined by the
harness's signer library with no check at all. Its "device" is a sleep."""

from __future__ import annotations

import time

import numpy as np


class SleepPlane:
    def __init__(self, t: int, device_s: float = 0.02):
        self.t, self.device_s = t, device_s

    @staticmethod
    def bucket_lanes(lanes: int) -> int:
        return 1 << max(0, lanes - 1).bit_length()

    def pack_verify_inputs(self, pks, msgs, sigs):
        return (np.ones(len(pks), dtype=bool),)  # the live mask only

    def make_lane_rand(self, n):
        return None

    def verify_packed(self, arrays, rand, n):
        time.sleep(self.device_s)
        return [True] * n

    def verify_host(self, pks, msgs, sigs):
        return self.verify_packed(None, None, len(pks))

    def pack_inputs(self, pubshares, msgs, partials, group_pks, indices):
        return (list(partials), list(indices), np.ones(len(msgs), dtype=bool))

    def make_rand(self, v):
        return None

    def recombine_packed(self, args, rand, v):
        from benchmark import signer
        from charon_tpu.crypto import g1g2
        from charon_tpu.tbls.python_impl import sig_to_point

        time.sleep(self.device_s)
        sigs = [
            sig_to_point(signer.recombine_unchecked(
                {i: g1g2.g2_to_bytes(p) for i, p in zip(idx, row)}), subgroup_check=False)
            for row, idx in zip(args[0], args[1])
        ]
        return sigs, [True] * v

    def recombine_host(self, pubshares, msgs, partials, group_pks, indices):
        return self.recombine_packed((partials, indices), None, len(msgs))


class Checked:
    """The tenant's handle with the verdicts SleepPlane cannot give: each
    verify lane is also checked by the process's tbls (the C++ engine in
    a host-only node), so a forged partial is rejected as on the chip."""

    def __init__(self, tenant):
        self._tenant, self.t = tenant, tenant.t
        self.recombine = tenant.recombine

    async def verify(self, items, deadline=None):
        from charon_tpu import tbls

        sound = tbls.verify_batch(list(items))
        rode = await self._tenant.verify(items, deadline=deadline)
        return [a and b for a, b in zip(sound, rode)]


class Hinted(Checked):
    """Checked says nothing of `wave_hints`, so its submitters send none and
    every window waits out its timer; this one takes the hint and passes it
    on, as the tenant's own handle does on the chip."""

    wave_hints = True

    async def verify(self, items, deadline=None, wave=None):
        from charon_tpu import tbls

        sound = tbls.verify_batch(list(items))
        rode = await self._tenant.verify(items, deadline=deadline, wave=wave)
        return [a and b for a, b in zip(sound, rode)]


def host_plane(server, handle=None, window=0.05, window_max=0.2, plane=None) -> None:
    """Wire the node's submitters (ValidatorAPI, the ParSigEx verifier,
    SigAgg) to a tenant of a CryptoPlaneService over SleepPlane, bridged
    into the node's own tracer as app/run.build_node does for a real
    plane. `server.coalescer` stays None: run.py loads no program.
    `handle` is the class the submitters hold the tenant through (Checked;
    Hinted passes the wave hints on), `plane` the plane's (SleepPlane); the
    windows default to 50 / 200 ms."""
    from charon_tpu.app import tracer
    from charon_tpu.core.cryptoplane import SlotCoalescer
    from charon_tpu.core.cryptosvc import CryptoPlaneService
    from charon_tpu.p2p.adapters import PARSIGEX_PROTOCOL

    node, run = server.node, server.run
    bridge = tracer.plane_span_bridge(node.tracer)

    def stats_hook(stats):
        run.flushes.append((time.time(), stats))
        bridge(stats)

    coalescer = SlotCoalescer(
        (plane or SleepPlane)(server.plan.threshold), window=window, window_max=window_max,
        decode_workers=2, stats_hook=stats_hook)
    service = CryptoPlaneService(coalescer, tracer=node.tracer)
    plane = (handle or Checked)(service.register("rehearsal"))
    parsigex = node.p2p._handlers[PARSIGEX_PROTOCOL].__self__.local
    node.vapi.plane = parsigex.verifier.plane = node.sigagg.plane = plane
    node.sigagg.pubshares_by_idx = node.pubshares_by_idx
    teardown = server.teardown

    async def close_then_teardown():
        late = await teardown()
        service.close()
        coalescer.close()
        return late

    server.teardown = close_then_teardown
