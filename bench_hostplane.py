#!/usr/bin/env python
"""bench_hostplane.py — event-loop stall + pipeline overlap microbench
for the coalescer's pipelined host plane (ISSUE 3 acceptance).

Simulates a slot-tick burst of partial-signature verifies hitting the
SlotCoalescer and measures, for the pre-pipeline synchronous decode path
(decode_workers=0 — decompression + hash-to-curve inline on the event
loop) vs the pipelined decode pool:

  * event-loop max stall — a 1 ms asyncio ticker's worst scheduling gap
    while the burst decodes (the QBFT/p2p latency the node would eat);
  * submit -> result latency per submission;
  * pipeline overlap — wall-clock seconds the decode/pack stages of
    window k ran while the device still executed window k-1 (> 0 only
    with double-buffered flushes).

The device is a wall-clock fake (SimPlane sleeps a configurable program
time and records busy spans), so the bench isolates HOST plane behavior
and runs without jax — CPU-only, CI-safe. Real decode work is used:
pure-python point decompression and hash-to-curve, the exact bigint
work the decode pool exists to move off the loop.

Decode A/B (ISSUE 5): `--decode-mode {python,device}` selects the
coalescer's signature-decode rung for the phases above, and the bench
always measures the decode stage's host CPU time for BOTH rungs over
the same burst (pk/msg caches warm — the live regime where signature
decompression dominates). With --decode-mode device (or --smoke) the
run FAILS unless the device rung cuts decode host CPU by
--assert-decode-ratio (default 5x), measured twice before concluding.

Cold-start A/B (ISSUE 6): `--cold-start` measures the COLD path — a
cache-flushed burst where every message pays hash-to-curve — as host
CPU per burst for the python rung (full SSWU + isogeny + cofactor
clearing per message, `crypto/h2c.py`) vs the device path's host half
(`ops/sswu.hash_to_field_lane`: expand_message_xmd + hash_to_field,
SHA-256 only — the field work ships to the batched device kernel).
The run FAILS unless the device path cuts cold-burst host CPU by
--assert-h2c-ratio (default 5x, measured twice before concluding).
Passed alone it runs just the A/B (a quick sizing tool for the
`--crypto-plane-warmup` flag); `--smoke` includes the gate.

Multi-tenant A/B (ISSUE 8): `--tenants` drives the core/cryptosvc
service with a victim tenant running paced duty bursts and a flooder
tenant pouring fire-and-forget bursts far over its admission quota,
over the same SimPlane device. The run FAILS unless (a) the flooder's
over-budget work actually sheds (PlaneOverloadError fail-fast) and
(b) the victim's p99 submit->result latency under flood stays below
--assert-tenant-ratio (default 2x) of its unflooded baseline — the
jax-free isolation gate ci.sh's chaos/hostplane tiers ride.

Observability overhead A/B (ISSUE 19): `--profiler` measures mean
verify latency with the flight recorder + plane profiler chained on
the coalescer's stats_hook path vs the bare coalescer at --lanes
lanes, and FAILS unless the instrumented run stays within
--assert-profiler-ratio (default 1.05x — the "within 5%" acceptance)
AND the profiler's per-family seconds account for the device's busy
time within 10%. `--smoke` includes the gate.

`--smoke` (ci.sh fast tier) runs tiny shapes and FAILS (exit 1) when
the stall improvement ratio drops below --assert-ratio or the overlap
hits zero — the event-loop-stall regression guard.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import threading
import time

import numpy as np


class SimPlane:
    """Wall-clock device stand-in: each flush 'executes' for device_s
    seconds and records its busy span. `busy` (threading.Event) lets the
    driver submit the next window precisely while a program is in
    flight. Exposes the packed AND parsed plane APIs (as fakes) so the
    coalescer exercises its real pack stage and decode_mode=device
    routing without jax."""

    def __init__(self, t: int, device_s: float):
        self.t = t
        self.device_s = device_s
        self.spans: list[tuple[float, float]] = []
        self.busy = threading.Event()

    def _device(self, n: int):
        t0 = time.monotonic()
        self.busy.set()
        time.sleep(self.device_s)
        self.busy.clear()
        self.spans.append((t0, time.monotonic()))

    def verify_host(self, pks, msgs, sigs, rng=None):
        self._device(len(pks))
        return [True] * len(pks)

    def recombine_host(self, pubshares, msgs, partials, group_pks,
                       indices, rng=None):
        self._device(len(msgs))
        return [None] * len(msgs), [True] * len(msgs)

    # -- packed / parsed fakes (lane counts only; live mask last) ---------

    def pack_verify_inputs(self, pks, msgs, sigs):
        return ("v", np.empty(len(pks)))

    def pack_verify_inputs_parsed(self, pks, msgs, parsed, sets=None):
        return ("vp", np.empty(len(pks)))

    def make_lane_rand(self, n: int, rng=None):
        return n

    def verify_packed(self, arrays, rand, n: int):
        self._device(n)
        return [True] * n

    verify_packed_parsed = verify_packed

    def pack_inputs(self, pubshares, msgs, partials, group_pks, indices):
        return ("r", np.empty(len(msgs)))

    pack_inputs_parsed = pack_inputs

    def make_rand(self, v: int, rng=None):
        return v

    def recombine_packed(self, args, rand, v: int):
        self._device(v)
        return [None] * v, [True] * v

    recombine_packed_parsed = recombine_packed


def _merge(spans):
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_seconds(a, b) -> float:
    """Total intersection length between two span lists."""
    total = 0.0
    for s1, e1 in _merge(a):
        for s2, e2 in _merge(b):
            total += max(0.0, min(e1, e2) - max(s1, s2))
    return total


def make_burst(lanes: int):
    """lanes distinct (pk, root, sig) items: distinct roots so every
    lane pays hash-to-curve, distinct sigs so every lane pays
    decompression (the caches only amortize the pubkey, as live traffic
    does)."""
    from charon_tpu.tbls.python_impl import PythonImpl

    impl = PythonImpl()
    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    items = []
    for i in range(lanes):
        root = i.to_bytes(32, "big")
        items.append((pk, root, impl.sign(sk, root)))
    return items


def _clear_decode_caches():
    from charon_tpu.tbls.tpu_impl import _cached_msg_point, _cached_pubkey_point

    _cached_msg_point.cache_clear()
    _cached_pubkey_point.cache_clear()


async def _stall_probe(stop: asyncio.Event, interval: float = 0.001):
    """Worst scheduling gap of a 1 ms ticker — the event-loop stall."""
    max_gap = 0.0
    last = time.monotonic()
    while not stop.is_set():
        await asyncio.sleep(interval)
        now = time.monotonic()
        max_gap = max(max_gap, now - last - interval)
        last = now
    return max_gap


async def run_phase(
    items, decode_workers: int, submissions: int, window: float,
    device_s: float, decode_mode: str = "python",
) -> dict:
    from charon_tpu.core.cryptoplane import SlotCoalescer

    _clear_decode_caches()
    plane = SimPlane(t=3, device_s=device_s)
    # per-flush stage spans travel on FlushStats (the same fields the
    # tracer bridge consumes) — collect them via the stats hook
    stats: list = []
    coal = SlotCoalescer(
        plane,
        window=window,
        decode_workers=decode_workers,
        stats_hook=stats.append,
        decode_mode=decode_mode,
    )
    stop = asyncio.Event()
    probe = asyncio.create_task(_stall_probe(stop))
    await asyncio.sleep(0.05)  # let the ticker settle

    # window k: the slot-tick burst, split across concurrent submissions
    # (ParSigEx inbound sets / VC pubshare checks / SigAgg)
    half = items[: len(items) // 2]
    k = max(1, len(half) // submissions)
    chunks = [half[i : i + k] for i in range(0, len(half), k)]
    t0 = time.monotonic()
    latencies: list[float] = []

    async def submit(chunk):
        ts = time.monotonic()
        res = await coal.verify(chunk)
        latencies.append(time.monotonic() - ts)
        return res

    first = asyncio.gather(*(submit(c) for c in chunks))

    # window k+1: submitted the moment window k's device program starts,
    # so its decode/pack stages can only proceed concurrently with the
    # in-flight program when the plane double-buffers
    async def second_window():
        while not plane.busy.is_set():
            await asyncio.sleep(0.001)
        return await submit(items[len(items) // 2 :])

    res2 = await second_window()
    res1 = await first
    wall = time.monotonic() - t0
    stop.set()
    stall = await probe
    assert all(all(r) for r in res1) and all(res2)
    coal.close()

    host_spans = [sp for s in stats for sp in s.decode_spans]
    host_spans += [s.pack_span for s in stats if s.pack_span is not None]
    device_spans = [s.device_span for s in stats if s.device_span is not None]
    return {
        "decode_workers": decode_workers,
        "decode_mode": decode_mode,
        "decode_device_lanes": sum(s.decode_device_lanes for s in stats),
        "decode_python_lanes": sum(s.decode_python_lanes for s in stats),
        "decode_cache_lookups": sum(s.decode_cache_lanes for s in stats),
        "lanes": len(items),
        "submissions": len(chunks) + 1,
        "flushes": coal.flushes,
        "wall_seconds": round(wall, 4),
        "loop_max_stall_seconds": round(stall, 4),
        "submit_latency_max_seconds": round(max(latencies), 4),
        "submit_latency_mean_seconds": round(
            sum(latencies) / len(latencies), 4
        ),
        "host_device_overlap_seconds": round(
            overlap_seconds(host_spans, device_spans), 4
        ),
        "overlapped_flushes": coal.overlapped_flushes,
        "max_inflight": coal.max_inflight,
    }


async def _measure(args, items):
    sync = await run_phase(
        items, 0, args.submissions, args.window, args.device_seconds,
        args.decode_mode,
    )
    piped = await run_phase(
        items, args.decode_workers, args.submissions, args.window,
        args.device_seconds, args.decode_mode,
    )
    ratio = sync["loop_max_stall_seconds"] / max(
        piped["loop_max_stall_seconds"], 1e-6
    )
    return sync, piped, ratio


def measure_decode_host(items, mode: str) -> float:
    """Host CPU seconds (thread_time — scheduler noise excluded) the
    decode stage spends on one burst under the given rung, pk/msg
    caches warm: cluster pubshares are a static cached set and live
    duty roots were hashed by earlier submissions in the slot, so what
    this isolates is exactly the always-fresh SIGNATURE decompression
    the device rung retires from the host (ISSUE 5)."""
    from charon_tpu.core.cryptoplane import (
        _decode_pubkey,
        _decode_verify_lane,
        _msg_point,
        _parse_verify_lane,
    )

    for pk, root, _sig in items:
        _decode_pubkey(pk)
        _msg_point(root)
    fn = _parse_verify_lane if mode == "device" else _decode_verify_lane
    t0 = time.thread_time()
    lanes = [fn(it) for it in items]
    elapsed = time.thread_time() - t0
    assert all(lane is not None for lane in lanes)
    return elapsed


def h2c_cold_ab(lanes: int) -> dict:
    """The Round-8 A/B: host CPU for a cache-flushed message burst —
    python hash-to-curve (what every cache miss pays today) vs the
    host half of the device path (SHA-256 hashing only; SSWU +
    3-isogeny + psi cofactor clearing run as ONE batched device
    program). thread_time, so scheduler noise is excluded; both sides
    see the same fresh messages (no cache can help either)."""
    from charon_tpu.ops import sswu
    from charon_tpu.tbls.tpu_impl import _decode_msg_point

    msgs = [b"cold-%d" % i for i in range(lanes)]
    t0 = time.thread_time()
    for m in msgs:
        _decode_msg_point(m)  # full python h2c — bypasses the cache
    py_s = time.thread_time() - t0
    t0 = time.thread_time()
    hashed = [sswu.hash_to_field_lane(m) for m in msgs]
    dev_s = time.thread_time() - t0
    assert len(hashed) == lanes
    return {
        "lanes": lanes,
        "python_h2c_host_seconds": round(py_s, 4),
        "device_h2c_host_seconds": round(dev_s, 6),
        "h2c_host_cpu_ratio": round(py_s / max(dev_s, 1e-9), 1),
        "python_ms_per_lane": round(py_s / lanes * 1000, 2),
    }


def decode_ab(items) -> dict:
    """The Round-7 A/B: decode-stage host CPU per burst, python rung vs
    device rung (parse-only host work; field arithmetic on device)."""
    py_s = measure_decode_host(items, "python")
    dev_s = measure_decode_host(items, "device")
    return {
        "lanes": len(items),
        "python_decode_host_seconds": round(py_s, 4),
        "device_decode_host_seconds": round(dev_s, 6),
        "decode_host_cpu_ratio": round(py_s / max(dev_s, 1e-9), 1),
    }


def _run_h2c_gate(lanes: int, want: float) -> tuple[dict, bool]:
    """Measure the cold-start h2c A/B, remeasuring once before failing
    the gate (CI-noise discipline shared with the other gates)."""
    ab = h2c_cold_ab(lanes)
    if want and ab["h2c_host_cpu_ratio"] < want:
        print(f"# h2c cold ratio {ab['h2c_host_cpu_ratio']}x < "
              f"{want}x — remeasuring")
        ab = h2c_cold_ab(lanes)
    ok = not want or ab["h2c_host_cpu_ratio"] >= want
    print(
        f"# cold-start h2c host CPU/burst ({ab['lanes']} lanes): python "
        f"{ab['python_h2c_host_seconds'] * 1000:.0f} ms "
        f"({ab['python_ms_per_lane']} ms/lane) -> device-path host "
        f"{ab['device_h2c_host_seconds'] * 1000:.1f} ms "
        f"({ab['h2c_host_cpu_ratio']}x)"
    )
    return ab, ok


async def _tenant_phase(items, flood: bool, duties: int, device_s: float):
    """One service run: victim duties (p99 latency measured) with or
    without a concurrent flooding tenant. The flooder's quota is a
    fraction of its offered load, so most of its work sheds at
    admission and the admitted remainder trickles through its
    weighted-fair budget."""
    from charon_tpu.core.cryptoplane import SlotCoalescer
    from charon_tpu.core.cryptosvc import (
        CryptoPlaneService,
        PlaneOverloadError,
        TenantQuota,
    )

    _clear_decode_caches()
    plane = SimPlane(t=3, device_s=device_s)
    # device decode rung (parse-only host work): the A/B isolates the
    # SERVICE's scheduling behavior, not python bigint decode — on the
    # python rung the flooder's admitted lanes would saturate the host
    # CPU with decompression, which is the decode gate's job to measure
    coal = SlotCoalescer(
        plane, window=0.01, decode_workers=2, decode_mode="device"
    )
    # round length ~2.5x the device program: the flooder's admitted
    # remainder (one budget's worth per round, usually ONE flush) can
    # never saturate the serialized device lane — admission control is
    # exactly the flow control that keeps the victim's flush from
    # queueing behind an unbounded flooder backlog
    svc = CryptoPlaneService(
        coal, round_lanes=64, round_interval=device_s * 2.5
    )
    victim = svc.register("victim", TenantQuota())
    flooder = svc.register(
        "flooder", TenantQuota(max_queue_jobs=8, max_queue_lanes=64)
    )
    stop = asyncio.Event()

    async def flood_loop():
        pending: set[asyncio.Task] = set()
        while not stop.is_set():
            for _ in range(4):

                async def burst():
                    try:
                        await flooder.verify(items * 4)
                    except PlaneOverloadError:
                        pass

                task = asyncio.create_task(burst())
                pending.add(task)
                task.add_done_callback(pending.discard)
            await asyncio.sleep(0.002)
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)

    flood_task = asyncio.create_task(flood_loop()) if flood else None
    latencies: list[float] = []
    try:
        for i in range(duties + 3):
            t0 = time.monotonic()
            res = await victim.verify(
                list(items), deadline=time.time() + 5.0
            )
            if i >= 3:  # first duties pay cold point-cache decodes
                latencies.append(time.monotonic() - t0)
            assert all(res)
            await asyncio.sleep(device_s * 2)
    finally:
        stop.set()
        if flood_task is not None:
            await flood_task
        svc.close()
        coal.close()
    latencies.sort()
    p99 = latencies[min(len(latencies) - 1, int(len(latencies) * 0.99))]
    return {
        "p99_seconds": round(p99, 4),
        "mean_seconds": round(sum(latencies) / len(latencies), 4),
        "flooder_shed_jobs": sum(svc.tenant("flooder").shed.values()),
        "flooder_admitted_lanes": svc.tenant("flooder").admitted_lanes,
        "victim_shed_jobs": sum(svc.tenant("victim").shed.values()),
    }


async def tenants_ab(args) -> tuple[dict, bool]:
    """Victim p99 with vs without the flood, plus the shed assertion
    (remeasured once before a verdict — CI-noise discipline)."""
    items = make_burst(8)
    duties = 20 if args.smoke else 30

    async def measure():
        base = await _tenant_phase(items, False, duties, 0.02)
        flooded = await _tenant_phase(items, True, duties, 0.02)
        ratio = flooded["p99_seconds"] / max(base["p99_seconds"], 1e-6)
        return base, flooded, ratio

    base, flooded, ratio = await measure()
    want = args.assert_tenant_ratio
    if want and (
        ratio >= want or flooded["flooder_shed_jobs"] == 0
    ):
        print(f"# tenant ratio {ratio:.2f}x (want < {want}x), shed "
              f"{flooded['flooder_shed_jobs']} — remeasuring")
        base, flooded, ratio = await measure()
    ok = not want or (
        ratio < want
        and flooded["flooder_shed_jobs"] > 0
        and flooded["victim_shed_jobs"] == 0
    )
    report = {
        "baseline": base,
        "flooded": flooded,
        "victim_p99_ratio": round(ratio, 2),
    }
    print(
        f"# tenant isolation: victim p99 "
        f"{base['p99_seconds'] * 1000:.0f} ms -> "
        f"{flooded['p99_seconds'] * 1000:.0f} ms under flood "
        f"({ratio:.2f}x, want < {want}x), flooder shed "
        f"{flooded['flooder_shed_jobs']} jobs / admitted "
        f"{flooded['flooder_admitted_lanes']} lanes"
    )
    return report, ok


async def _profiler_phase(items, duties: int, device_s: float,
                          instrumented: bool):
    """Mean submit->result latency for `duties` verify bursts through
    the coalescer — with or without the full ISSUE 19 observability
    chain (flight recorder stats hook + plane profiler) on the
    stats_hook path. Returns the profiler's per-family attribution and
    the device's true busy seconds for the accounting gate."""
    from charon_tpu.core.cryptoplane import SlotCoalescer

    _clear_decode_caches()
    plane = SimPlane(t=3, device_s=device_s)
    rec = prof = None
    hook = None
    if instrumented:
        from charon_tpu.app.flightrec import FlightRecorder, stats_hook
        from charon_tpu.app.planeprof import PlaneProfiler

        rec = FlightRecorder(node="bench")
        prof = PlaneProfiler()
        hook = stats_hook(rec, inner=prof.stats_hook())
    coal = SlotCoalescer(
        plane, window=0.01, decode_workers=2, decode_mode="device",
        stats_hook=hook,
    )
    latencies: list[float] = []
    try:
        for i in range(duties + 3):
            t0 = time.monotonic()
            res = await coal.verify(list(items))
            if i >= 3:  # first duties pay cold point-cache decodes
                latencies.append(time.monotonic() - t0)
            assert all(res)
    finally:
        coal.close()
    out = {
        "mean_seconds": round(sum(latencies) / len(latencies), 4),
        "max_seconds": round(max(latencies), 4),
        "device_busy_seconds": round(
            sum(e - s for s, e in plane.spans), 4
        ),
    }
    if instrumented:
        out["family_seconds"] = round(sum(prof.kernel_seconds.values()), 4)
        out["profiled_flushes"] = prof.flushes
        out["recorded_events"] = len(rec)
    return out


async def profiler_ab(args) -> tuple[dict, bool]:
    """Observability overhead gate (ISSUE 19): the always-on flight
    recorder + plane profiler must hold mean burst latency within
    --assert-profiler-ratio of the bare coalescer, AND the profiler's
    per-family seconds must account for the device's busy time within
    10% (remeasured before a verdict — CI-noise discipline)."""
    items = make_burst(args.lanes)
    duties = 12 if args.smoke else 20

    async def measure():
        bare = await _profiler_phase(items, duties, 0.02, False)
        inst = await _profiler_phase(items, duties, 0.02, True)
        ratio = inst["mean_seconds"] / max(bare["mean_seconds"], 1e-6)
        return bare, inst, ratio

    bare, inst, ratio = await measure()
    want = args.assert_profiler_ratio
    attempts = 1
    while want and ratio >= want and attempts < 3:
        print(f"# profiler overhead {ratio:.3f}x (want < {want}x) — "
              f"remeasuring (attempt {attempts + 1}/3)")
        bare, inst, ratio = await measure()
        attempts += 1
    # accounting: SimPlane has no program hook, so every flush lands on
    # the synthetic 'device' family — the per-family sum must equal the
    # device's true busy seconds within 10%
    busy = inst["device_busy_seconds"]
    acct_err = abs(inst["family_seconds"] - busy) / max(busy, 1e-9)
    ok = (
        (not want or ratio < want)
        and acct_err <= 0.10
        and inst["profiled_flushes"] > 0
        and inst["recorded_events"] >= inst["profiled_flushes"]
    )
    report = {
        "lanes": len(items),
        "bare": bare,
        "instrumented": inst,
        "overhead_ratio": round(ratio, 3),
        "family_accounting_error": round(acct_err, 4),
        "measure_attempts": attempts,
    }
    print(
        f"# profiler overhead: mean {bare['mean_seconds'] * 1000:.1f} ms "
        f"bare -> {inst['mean_seconds'] * 1000:.1f} ms instrumented "
        f"({ratio:.3f}x, want < {want}x); per-family seconds "
        f"{inst['family_seconds']:.3f}s vs device busy {busy:.3f}s "
        f"({acct_err * 100:.1f}% error, want <= 10%)"
    )
    return report, ok


async def _remote_phase(items, duties: int, device_s: float,
                        remote: bool):
    """Mean submit->result latency for `duties` verify bursts through
    core/cryptosvc — either holding the TenantPlane directly
    (in-process baseline) or dialing it through the full socket path
    (cryptosvc_server + cryptosvc_client on localhost)."""
    from charon_tpu.core.cryptoplane import SlotCoalescer
    from charon_tpu.core.cryptosvc import CryptoPlaneService, TenantQuota

    _clear_decode_caches()
    plane = SimPlane(t=3, device_s=device_s)
    coal = SlotCoalescer(
        plane, window=0.01, decode_workers=2, decode_mode="device"
    )
    svc = CryptoPlaneService(coal, round_lanes=4096)
    tenant = svc.register("bench", TenantQuota(max_queue_lanes=4096))
    server = client = None
    handle = tenant
    try:
        if remote:
            from charon_tpu.core.cryptosvc_client import RemotePlane
            from charon_tpu.core.cryptosvc_server import (
                CryptoServiceServer,
            )

            server = CryptoServiceServer(
                svc, {"bench": "bench-token"}, port=0
            )
            await server.start()
            client = RemotePlane(
                "127.0.0.1", server.port, "bench", "bench-token",
                local=tenant,
            )
            await client.start()
            # the A/B measures REMOTE dispatch: wait out the first
            # connect so no duty silently runs on the local rung
            for _ in range(200):
                if client.state != "down":
                    break
                await asyncio.sleep(0.01)
            handle = client
        latencies: list[float] = []
        for i in range(duties + 3):
            t0 = time.monotonic()
            res = await handle.verify(
                list(items), deadline=time.time() + 5.0
            )
            if i >= 3:  # first duties pay cold point-cache decodes
                latencies.append(time.monotonic() - t0)
            assert all(res)
        if remote:
            # a failover mid-bench would compare local against local
            assert client.remote_jobs >= duties, (
                f"only {client.remote_jobs}/{duties} duties dispatched "
                f"remotely (failovers: {client.failovers})"
            )
    finally:
        if client is not None:
            await client.close()
        if server is not None:
            await server.close()
        svc.close()
        coal.close()
    return {
        "mean_seconds": round(sum(latencies) / len(latencies), 4),
        "max_seconds": round(max(latencies), 4),
    }


async def remote_ab(args) -> tuple[dict, bool]:
    """Remote-dispatch overhead gate (ISSUE 17): the full socket path
    (codec frames + localhost TCP + stats briefs) must stay under
    --assert-remote-ratio of holding the TenantPlane in-process, at
    the full --lanes burst (remeasured once — CI-noise discipline)."""
    items = make_burst(args.lanes)
    duties = 12 if args.smoke else 20

    async def measure():
        local = await _remote_phase(items, duties, 0.02, False)
        remote = await _remote_phase(items, duties, 0.02, True)
        ratio = remote["mean_seconds"] / max(local["mean_seconds"], 1e-6)
        return local, remote, ratio

    local, remote, ratio = await measure()
    want = args.assert_remote_ratio
    if want and ratio >= want:
        print(f"# remote ratio {ratio:.2f}x (want < {want}x) — "
              f"remeasuring")
        local, remote, ratio = await measure()
    ok = not want or ratio < want
    report = {
        "lanes": len(items),
        "in_process": local,
        "remote": remote,
        "remote_overhead_ratio": round(ratio, 2),
    }
    print(
        f"# remote dispatch: mean {local['mean_seconds'] * 1000:.0f} ms "
        f"in-process -> {remote['mean_seconds'] * 1000:.0f} ms over "
        f"sockets ({ratio:.2f}x, want < {want}x) at {len(items)} lanes"
    )
    return report, ok


async def main(args) -> int:
    if args.profiler:
        # standalone observability overhead gate (ISSUE 19): jax-free,
        # SimPlane device, flight recorder + plane profiler on the
        # stats-hook path
        report, ok = await profiler_ab(args)
        print(json.dumps({"bench": "hostplane-profiler", **report},
                         indent=2))
        if not ok:
            print(
                f"FAIL: recorder+profiler overhead "
                f"{report['overhead_ratio']}x (want < "
                f"{args.assert_profiler_ratio}x) or family accounting "
                f"error {report['family_accounting_error']} > 0.10"
            )
            return 1
        print("profiler PASS")
        return 0
    if args.remote:
        # remote crypto-plane dispatch overhead gate (ISSUE 17):
        # jax-free, SimPlane device, real sockets on localhost
        report, ok = await remote_ab(args)
        print(json.dumps({"bench": "hostplane-remote", **report},
                         indent=2))
        if not ok:
            print(
                f"FAIL: remote dispatch overhead "
                f"{report['remote_overhead_ratio']}x (want < "
                f"{args.assert_remote_ratio}x in-process)"
            )
            return 1
        print("remote PASS")
        return 0
    if args.tenants:
        # standalone multi-tenant isolation gate (ISSUE 8): jax-free,
        # SimPlane device — the ci.sh chaos/hostplane tiers' A/B
        report, ok = await tenants_ab(args)
        print(json.dumps({"bench": "hostplane-tenants", **report},
                         indent=2))
        if not ok:
            print(
                f"FAIL: flooding tenant degraded victim p99 "
                f"{report['victim_p99_ratio']}x (want < "
                f"{args.assert_tenant_ratio}x) or shed nothing"
            )
            return 1
        print("tenants PASS")
        return 0
    lanes = 32 if args.smoke else args.lanes
    if args.cold_start and not args.smoke:
        # standalone cold-start A/B: the sizing tool for
        # --crypto-plane-warmup (docs/operations.md), gated like smoke
        ab, ok = _run_h2c_gate(lanes, args.assert_h2c_ratio)
        print(json.dumps({"bench": "hostplane-cold-start",
                          "h2c_cold_ab": ab}, indent=2))
        if not ok:
            print(f"FAIL: device h2c path cut cold-burst host CPU only "
                  f"{ab['h2c_host_cpu_ratio']}x < {args.assert_h2c_ratio}x")
            return 1
        print("cold-start PASS")
        return 0
    print(f"# generating {lanes}-lane burst (pure-python signing) ...")
    t0 = time.monotonic()
    items = make_burst(lanes)
    print(f"# setup {time.monotonic() - t0:.1f}s")

    if args.device_seconds <= 0:
        # auto-calibrate: the simulated program must outlast window
        # k+1's decode (GIL makes pure-python decode effectively serial
        # across pool threads) or the double-buffering measurement
        # never engages. Measure per-lane decode cost, size the device
        # window to the second burst's decode wall plus margin.
        from charon_tpu.core.cryptoplane import _decode_verify_lane

        _clear_decode_caches()
        t0 = time.monotonic()
        for it in items[:8]:
            _decode_verify_lane(it)
        per_lane = (time.monotonic() - t0) / 8
        args.device_seconds = max(1.0, per_lane * (len(items) // 2) * 1.5)
        print(f"# calibrated device window: {args.device_seconds:.1f}s "
              f"({per_lane * 1000:.0f} ms/lane decode)")
    want = args.assert_ratio or (3.0 if args.smoke else 0.0)

    def gates_ok(piped, ratio):
        return (
            ratio >= want
            and piped["host_device_overlap_seconds"] > 0
            and piped["max_inflight"] >= 2
        )

    sync, piped, ratio = await _measure(args, items)
    # the gates are wall-clock: on a contended CI runner one noisy
    # measurement must not fail the tier — remeasure before concluding
    # a regression (a genuine one, e.g. decode back on the loop or a
    # serialized pipeline, fails every attempt)
    attempts = 1
    while want and not gates_ok(piped, ratio) and attempts < 3:
        print(f"# gates not met (ratio {ratio:.1f}x, inflight "
              f"{piped['max_inflight']}) — remeasuring "
              f"(attempt {attempts + 1}/3, load transient?)")
        sync, piped, ratio = await _measure(args, items)
        attempts += 1
    # decode-stage host CPU A/B (ISSUE 5) — measured twice before a
    # verdict sticks (the gate below fails only if BOTH runs miss)
    ab = decode_ab(items)
    want_decode = args.assert_decode_ratio if (
        args.smoke or args.decode_mode == "device"
    ) else 0.0
    decode_attempts = 1
    while want_decode and ab["decode_host_cpu_ratio"] < want_decode \
            and decode_attempts < 2:
        print(f"# decode ratio {ab['decode_host_cpu_ratio']}x < "
              f"{want_decode}x — remeasuring")
        ab = decode_ab(items)
        decode_attempts += 1
    # cold-start h2c A/B (ISSUE 6): measured AND gated only under
    # --smoke / --cold-start — a plain stall/overlap run should not pay
    # ~20 ms/lane of python hash-to-curve for an unenforced number
    h2c_ab, h2c_ok = None, True
    if args.smoke or args.cold_start:
        h2c_ab, h2c_ok = _run_h2c_gate(lanes, args.assert_h2c_ratio)
    # observability overhead gate (ISSUE 19): under --smoke the flight
    # recorder + profiler chain must stay within its latency budget and
    # account for the device's busy seconds
    prof_report, prof_ok = None, True
    if args.smoke:
        prof_report, prof_ok = await profiler_ab(args)
    report = {
        "bench": "hostplane",
        "smoke": args.smoke,
        "sync": sync,
        "pipelined": piped,
        "stall_improvement_ratio": round(ratio, 1),
        "measure_attempts": attempts,
        "decode_ab": ab,
        **({"h2c_cold_ab": h2c_ab} if h2c_ab else {}),
        **({"profiler_ab": prof_report} if prof_report else {}),
    }
    print(json.dumps(report, indent=2))
    print(
        f"# loop stall {sync['loop_max_stall_seconds'] * 1000:.0f} ms -> "
        f"{piped['loop_max_stall_seconds'] * 1000:.0f} ms  ({ratio:.0f}x), "
        f"host/device overlap {piped['host_device_overlap_seconds'] * 1000:.0f} ms, "
        f"inflight depth {piped['max_inflight']}"
    )
    print(
        f"# decode host CPU/burst: python "
        f"{ab['python_decode_host_seconds'] * 1000:.0f} ms -> device rung "
        f"{ab['device_decode_host_seconds'] * 1000:.1f} ms "
        f"({ab['decode_host_cpu_ratio']}x)"
    )
    if want_decode and ab["decode_host_cpu_ratio"] < want_decode:
        print(
            f"FAIL: device decode rung cut host CPU only "
            f"{ab['decode_host_cpu_ratio']}x < {want_decode}x "
            f"on {decode_attempts} attempts"
        )
        return 1
    if not h2c_ok:
        print(
            f"FAIL: device h2c path cut cold-burst host CPU only "
            f"{h2c_ab['h2c_host_cpu_ratio']}x < {args.assert_h2c_ratio}x"
        )
        return 1
    if not prof_ok:
        print(
            f"FAIL: recorder+profiler overhead "
            f"{prof_report['overhead_ratio']}x (want < "
            f"{args.assert_profiler_ratio}x) or family accounting "
            f"error {prof_report['family_accounting_error']} > 0.10"
        )
        return 1
    if want:
        if ratio < want:
            print(
                f"FAIL: stall improvement {ratio:.1f}x < {want}x "
                f"on {attempts} attempts (event-loop stall regression)"
            )
            return 1
        if piped["host_device_overlap_seconds"] <= 0:
            print("FAIL: no host/device overlap — pipeline broken")
            return 1
        if piped["max_inflight"] < 2:
            print(
                "FAIL: device lane never held 2 flushes — "
                "double-buffering broken"
            )
            return 1
        print("smoke PASS")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--lanes", type=int, default=256,
                    help="burst size (verify lanes)")
    ap.add_argument("--submissions", type=int, default=4,
                    help="concurrent submissions the first window splits into")
    ap.add_argument("--window", type=float, default=0.02)
    ap.add_argument("--decode-workers", type=int, default=4)
    ap.add_argument("--device-seconds", type=float, default=0.0,
                    help="simulated device program wall time per flush; "
                    "0 (default) auto-calibrates to outlast the next "
                    "window's decode so the double-buffering "
                    "measurement engages")
    ap.add_argument("--decode-mode", choices=("python", "device"),
                    default="python",
                    help="coalescer signature-decode rung for the "
                    "stall/overlap phases; 'device' also gates on the "
                    "decode host-CPU A/B ratio")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + regression assertions (CI fast tier)")
    ap.add_argument("--assert-ratio", type=float, default=0.0,
                    help="fail unless stall improves by at least this factor")
    ap.add_argument("--assert-decode-ratio", type=float, default=5.0,
                    help="with --decode-mode device or --smoke: fail "
                    "unless the device rung cuts decode-stage host CPU "
                    "by at least this factor (ISSUE 5 acceptance)")
    ap.add_argument("--cold-start", action="store_true",
                    help="cold-path A/B: cache-flushed h2c burst, "
                    "python hash-to-curve vs the device path's host "
                    "half; alone it runs just the A/B, with --smoke "
                    "the gate joins the tier")
    ap.add_argument("--assert-h2c-ratio", type=float, default=5.0,
                    help="with --cold-start or --smoke: fail unless "
                    "the device h2c path cuts cold-burst host CPU by "
                    "at least this factor (ISSUE 6 acceptance)")
    ap.add_argument("--tenants", action="store_true",
                    help="multi-tenant isolation A/B (ISSUE 8): victim "
                    "p99 flush latency with vs without a flooding "
                    "tenant through core/cryptosvc; gates on "
                    "--assert-tenant-ratio and on the flood shedding")
    ap.add_argument("--assert-tenant-ratio", type=float, default=2.0,
                    help="with --tenants: fail unless the victim "
                    "tenant's p99 latency under flood stays below this "
                    "multiple of its unflooded baseline")
    ap.add_argument("--remote", action="store_true",
                    help="remote crypto-plane A/B (ISSUE 17): mean "
                    "verify latency holding the TenantPlane in-process "
                    "vs dialing it through cryptosvc_server/_client "
                    "over localhost sockets at --lanes lanes")
    ap.add_argument("--assert-remote-ratio", type=float, default=2.0,
                    help="with --remote: fail unless the socket path "
                    "stays below this multiple of in-process dispatch")
    ap.add_argument("--profiler", action="store_true",
                    help="observability overhead A/B (ISSUE 19): mean "
                    "verify latency with the flight recorder + plane "
                    "profiler on the stats-hook path vs the bare "
                    "coalescer at --lanes lanes; also asserts the "
                    "profiler's per-family seconds account for the "
                    "device busy time within 10%%")
    ap.add_argument("--assert-profiler-ratio", type=float, default=1.05,
                    help="with --profiler or --smoke: fail unless the "
                    "instrumented mean latency stays below this "
                    "multiple of the bare coalescer (ISSUE 19 "
                    "acceptance: within 5%%)")
    raise SystemExit(asyncio.run(main(ap.parse_args())))
