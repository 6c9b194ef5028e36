"""Pipelined host plane (ISSUE 3): decode pool threading, adaptive /
deadline-aware windows, the packed two-stage flush, shape-bucket
discipline (bounded jit-cache growth), and the tpu_impl point-cache LRU
contract the decode pool leans on.

Device work stays faked or trivially-jitted (pairing math monkeypatched
before any trace) so this file is compile-free fast tier.
"""

from __future__ import annotations

import asyncio
import threading
import time

import pytest

from charon_tpu.core.cryptoplane import SlotCoalescer
from charon_tpu.tbls.python_impl import PythonImpl
from tests.test_cryptoplane import FakePlane, T


def _sig_items(n: int, distinct_roots: bool = True):
    impl = PythonImpl()
    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    items = []
    for i in range(n):
        root = (i if distinct_roots else 0).to_bytes(32, "big")
        items.append((pk, root, impl.sign(sk, root)))
    return items


def _decode_threads() -> list[threading.Thread]:
    return [
        t for t in threading.enumerate() if t.name.startswith("crypto-decode")
    ]


# ---------------------------------------------------------------------------
# decode pool
# ---------------------------------------------------------------------------


def test_decode_pool_results_match_sync_path():
    """Off-loop decode produces byte-identical verdicts to the inline
    path, including malformed lanes that must fail on host."""
    items = _sig_items(3)
    items.append((items[0][0], b"\x01" * 32, b"\x00" * 96))  # bad sig

    def run(workers):
        plane = SlotCoalescer(FakePlane(T), window=0.01, decode_workers=workers)
        try:
            return asyncio.run(plane.verify(items))
        finally:
            plane.close()

    assert run(0) == run(2) == [True, True, True, False]


def test_no_decode_threads_until_used_and_none_when_disabled():
    """The un-instrumented path owns no threads: a coalescer never
    creates the decode pool before its first submission, and
    decode_workers=0 (plane pipelining disabled) never creates it at
    all — only the serialized device lane exists."""
    assert not _decode_threads()
    idle = SlotCoalescer(FakePlane(T), window=0.01)
    assert idle._decode_pool is None and not _decode_threads()
    idle.close()

    off = SlotCoalescer(FakePlane(T), window=0.01, decode_workers=0)
    assert asyncio.run(off.verify(_sig_items(1))) == [True]
    assert off._decode_pool is None and not _decode_threads()
    off.close()

    on = SlotCoalescer(FakePlane(T), window=0.01, decode_workers=2)
    assert asyncio.run(on.verify(_sig_items(1))) == [True]
    assert len(_decode_threads()) >= 1
    on.close()


def test_recombine_decodes_off_loop(monkeypatch):
    """recombine() rows decode on the pool too, with prefail isolation
    preserved (the bad row never ships; the good row still lands)."""
    from charon_tpu.crypto import shamir

    impl = PythonImpl()
    secret = impl.generate_secret_key()
    shares = impl.threshold_split(secret, 4, T)
    gpk = impl.secret_to_public_key(secret)
    root = b"\x21" * 32
    partials = [impl.sign(shares[i], root) for i in (1, 2, 3)]
    pubshares = [impl.secret_to_public_key(shares[i]) for i in (1, 2, 3)]
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=0.01, decode_workers=2)

    async def main():
        return await plane.recombine(
            [pubshares, pubshares],
            [root, root],
            [partials, [b"\xff" * 96] * 3],  # second row: undecodable
            [gpk, gpk],
            [[1, 2, 3], [1, 2, 3]],
        )

    sigs, oks = asyncio.run(main())
    plane.close()
    assert oks == [True, False]
    assert sigs[0] is not None and sigs[1] is None
    assert fake.recombine_lane_count == 1  # prefail row skipped, not shipped
    impl.verify(gpk, root, sigs[0])


# ---------------------------------------------------------------------------
# adaptive + deadline-aware window
# ---------------------------------------------------------------------------


def test_window_grows_under_load_and_decays_when_idle():
    plane = SlotCoalescer(FakePlane(T), window=0.005, window_max=0.05)
    items = _sig_items(1)

    async def burst():
        await asyncio.gather(plane.verify(items), plane.verify(items))

    base = plane.current_window
    asyncio.run(burst())  # 2 jobs in one window -> grow
    grown = plane.current_window
    assert grown > base
    for _ in range(6):  # single quiet jobs -> decay back to base
        asyncio.run(plane.verify(items))
    plane.close()
    assert plane.current_window == pytest.approx(base)
    assert plane.current_window <= grown


def test_deadline_pulls_flush_earlier():
    """A submission whose duty deadline would overshoot the window
    flushes early instead of waiting the window out."""
    plane = SlotCoalescer(FakePlane(T), window=5.0, window_min=0.001)
    items = _sig_items(1)

    async def main():
        t0 = time.monotonic()
        await plane.verify(items, deadline=time.time() + 0.05)
        return time.monotonic() - t0

    elapsed = asyncio.run(main())
    plane.close()
    assert elapsed < 2.0, f"deadline ignored: flush took {elapsed:.2f}s"


def test_late_tighter_deadline_rearms_armed_flush():
    """A tighter deadline arriving while the window timer sleeps pulls
    the ALREADY-ARMED flush earlier (both jobs share one program)."""
    fake = FakePlane(T)
    plane = SlotCoalescer(fake, window=5.0, window_min=0.001)
    items = _sig_items(1)

    async def main():
        t0 = time.monotonic()
        slow = asyncio.create_task(plane.verify(items))
        await asyncio.sleep(0.05)
        fast = asyncio.create_task(
            plane.verify(items, deadline=time.time() + 0.05)
        )
        await asyncio.gather(slow, fast)
        return time.monotonic() - t0

    elapsed = asyncio.run(main())
    plane.close()
    assert elapsed < 2.0
    assert fake.verify_calls == 1  # still ONE coalesced program


# ---------------------------------------------------------------------------
# packed two-stage flush + stats
# ---------------------------------------------------------------------------


class PackedFakePlane(FakePlane):
    """FakePlane that also speaks the packed two-stage API the real
    SlotCryptoPlane exposes, with bucket padding, so the fast tier
    exercises the pipelined pack/device split."""

    def __init__(self, t):
        super().__init__(t)
        self.pack_calls = 0
        self.packed_calls = 0

    def _bucket(self, n):
        from charon_tpu.ops import blsops

        return blsops.bucket_lanes(n)

    def pack_verify_inputs(self, pks, msgs, sigs):
        self.pack_calls += 1
        n = len(pks)

        class _Live:  # minimal shape-carrying stand-in
            shape = (self._bucket(n),)

        return list(pks), list(msgs), list(sigs), _Live()

    def make_lane_rand(self, n, rng=None):
        return [1] * self._bucket(n)

    def verify_packed(self, arrays, rand, n):
        self.packed_calls += 1
        self.verify_calls += 1
        self.verify_lane_count += n
        return [True] * n

    def pack_inputs(self, pubshares, msgs, partials, group_pks, indices):
        self.pack_calls += 1
        v = len(msgs)

        class _Live:
            shape = (self._bucket(v),)

        return (pubshares, msgs, partials, group_pks, indices, _Live())

    def make_rand(self, v, rng=None):
        return [1] * self._bucket(v)

    def recombine_packed(self, args, rand, v):
        from charon_tpu.crypto import shamir

        self.packed_calls += 1
        self.recombine_calls += 1
        self.recombine_lane_count += v
        _, _, partials, _, indices, _ = args
        sigs = [
            shamir.threshold_aggregate_g2(dict(zip(idx, parts)))
            for idx, parts in zip(indices, partials)
        ]
        return sigs, [True] * v


def test_packed_flush_path_and_stats():
    """With a packed-API plane the flush packs on the decode pool and
    runs the device stage on the packed batch; FlushStats carries
    occupancy, bucket padding, and decode-queue delays."""
    fake = PackedFakePlane(T)
    stats = []
    plane = SlotCoalescer(
        fake, window=0.01, decode_workers=2, stats_hook=stats.append
    )
    items = _sig_items(3)

    async def main():
        r1, r2 = await asyncio.gather(
            plane.verify(items), plane.verify(items[:1])
        )
        return r1, r2

    r1, r2 = asyncio.run(main())
    plane.close()
    assert r1 == [True] * 3 and r2 == [True]
    assert fake.packed_calls == 1 and fake.pack_calls == 1
    assert fake.verify_calls == 1  # one coalesced program
    [s] = stats
    assert s.jobs == 2 and s.lanes == 4
    assert s.padded_lanes == 4  # bucket_lanes(4) == 4
    assert s.pad_lanes == 0
    assert s.decode_queue_seconds  # chunks went through the pool
    assert plane.coalesced_flushes == 1


def test_close_racing_flush_fails_waiters_without_degrading():
    """A flush landing after close() fails its waiters fast; the
    closed-executor error must NOT masquerade as a device failure and
    burn the process-wide msm-off rung."""
    from charon_tpu.ops import msm as MSM
    from charon_tpu.tbls import TblsError

    plane = SlotCoalescer(
        FakePlane(T), window=0.05, decode_workers=0,
        plane_factory=lambda: FakePlane(T),
    )
    items = _sig_items(1)

    async def main():
        task = asyncio.create_task(plane.verify(items))
        await asyncio.sleep(0)  # job decoded inline + flush armed
        plane.close()
        with pytest.raises(TblsError, match="closed"):
            await task

    try:
        assert MSM.msm_active()
        asyncio.run(main())
        assert MSM.msm_active(), "shutdown race must not flip MSM off"
        assert plane.host_fallback_flushes == 0
    finally:
        MSM.set_msm(None)


# ---------------------------------------------------------------------------
# shape buckets: flushes land on the declared ladder, jit cache bounded
# ---------------------------------------------------------------------------


def test_bucket_ladder_values():
    from charon_tpu.ops import blsops

    assert [blsops.bucket_lanes(n) for n in (1, 4, 5, 17, 100)] == [
        4, 4, 8, 32, 128,
    ]
    # sharded: divisible by the mesh AND on the pow2-per-shard ladder
    # (per-shard floor 1 — the shard count is already the batch floor)
    assert blsops.bucket_lanes(3, 8) == 8
    assert blsops.bucket_lanes(9, 8) == 16
    assert blsops.bucket_lanes(100, 8) == 128
    assert blsops.bucket_lanes(257, 8) == 512
    with pytest.raises(ValueError):
        blsops.bucket_lanes(4, 0)


@pytest.mark.filterwarnings("ignore")
def test_flushes_land_on_buckets_and_jit_cache_is_bounded(monkeypatch):
    """100 random-size verify flushes through the REAL SlotCryptoPlane
    pack path compile at most one program per bucket shape: kernel-cache
    growth is O(log max_batch), never O(flushes). Pairing math is
    monkeypatched to a trivial kernel BEFORE any trace so the test is
    compile-free; the jit cache accounting is the real one."""
    import random

    import jax.numpy as jnp

    from charon_tpu.ops import pairing as DP
    from charon_tpu.parallel.mesh import SlotCryptoPlane, make_mesh

    traced_shapes: list[int] = []

    def fake_verify_rlc(ctx, fr_ctx, pk, msg, sig, rand):
        import jax

        traced_shapes.append(jax.tree_util.tree_leaves(pk)[0].shape[0])
        return jnp.asarray(True)

    monkeypatch.setattr(DP, "batched_verify_rlc", fake_verify_rlc)
    plane = SlotCryptoPlane(make_mesh(), t=T)

    rng = random.Random(7)
    sizes = [rng.randrange(1, 150) for _ in range(100)]
    from charon_tpu.crypto.g1g2 import G1_GEN, G2_GEN

    for n in sizes:
        ok = plane.verify_host([G1_GEN] * n, [G2_GEN] * n, [G2_GEN] * n)
        assert ok == [True] * n

    ladder = {plane.bucket_lanes(n) for n in sizes}
    # tracing ran once per compiled program: every shape is a declared
    # bucket and the compile count == |ladder|, not |flushes| (inside
    # shard_map the trace sees the PER-SHARD slice of each bucket)
    shards = plane.shard_count()
    assert set(traced_shapes) == {b // shards for b in ladder}
    assert len(traced_shapes) == len(ladder) <= 8
    assert plane._verify_rlc._cache_size() == len(ladder)
    assert plane.jit_cache_size() == len(ladder)


def test_blsops_engine_pads_to_same_ladder(monkeypatch):
    """BlsEngine.verify_batch rides the same pow2 ladder: 50 random
    batch sizes -> at most one compiled program per bucket, measured by
    blsops.jit_cache_size()."""
    import random

    import jax
    import jax.numpy as jnp

    from charon_tpu.ops import blsops
    from charon_tpu.ops import pairing as DP

    def fake_verify(ctx, pk, msg, sig):
        return jnp.ones(jax.tree_util.tree_leaves(pk)[0].shape[0], bool)

    monkeypatch.setattr(DP, "batched_verify", fake_verify)
    blsops.clear_kernel_caches()  # rebuild wrappers over the fake
    try:
        engine = blsops.BlsEngine()
        rng = random.Random(11)
        sizes = [rng.randrange(1, 200) for _ in range(50)]
        from charon_tpu.crypto.g1g2 import G1_GEN, G2_GEN

        for n in sizes:
            ok = engine.verify_batch(
                [G1_GEN] * n, [G2_GEN] * n, [G2_GEN] * n
            )
            assert ok == [True] * n
        ladder = {blsops.bucket_lanes(n) for n in sizes}
        assert blsops.jit_cache_size() == len(ladder) <= 8
    finally:
        blsops.clear_kernel_caches()  # drop fakes for later tests


class ParsedFakePlane(FakePlane):
    """FakePlane + the packed AND parsed plane APIs, so the coalescer's
    decode_mode=device routing and its step-down ladder are drivable
    without jax. `fail_parsed` primes the next N parsed device calls to
    raise (the injected decode-kernel failure)."""

    def __init__(self, t: int, fail_parsed: int = 0):
        super().__init__(t)
        self.fail_parsed = fail_parsed
        self.parsed_verify_calls = 0

    def pack_verify_inputs(self, pks, msgs, sigs):
        import numpy as np

        return ("v", np.empty(len(pks)))

    def pack_verify_inputs_parsed(self, pks, msgs, parsed, sets=None):
        import numpy as np

        from charon_tpu.ops import decompress as DEC

        assert all(isinstance(p, DEC.ParsedPoint) for p in parsed)
        return ("vp", np.empty(len(pks)))

    def make_lane_rand(self, n: int, rng=None):
        return n

    def verify_packed(self, arrays, rand, n: int):
        return self.verify_host([None] * n, None, None)

    def verify_packed_parsed(self, arrays, rand, n: int):
        self.parsed_verify_calls += 1
        if self.fail_parsed > 0:
            self.fail_parsed -= 1
            raise RuntimeError("injected parsed-kernel failure")
        return [True] * n

    def pack_inputs(self, pubshares, msgs, partials, group_pks, indices):
        import numpy as np

        return ("r", np.empty(len(msgs)))

    pack_inputs_parsed = pack_inputs

    def make_rand(self, v: int, rng=None):
        return v

    def recombine_packed(self, args, rand, v: int):
        return [None] * v, [True] * v

    recombine_packed_parsed = recombine_packed


def test_decode_mode_device_routes_parsed_lanes():
    """decode_mode=device ships PARSED signature lanes to the parsed
    plane API; host-parse rejects still fail per-lane on host; stats
    carry the device decode-source breakdown."""
    stats = []
    plane = ParsedFakePlane(T)
    coal = SlotCoalescer(plane, window=0.01, decode_workers=0,
                         decode_mode="device", stats_hook=stats.append)
    items = _sig_items(3)
    items.append((items[0][0], b"\x01" * 32, b"\x00" * 96))  # bad flags
    try:
        assert asyncio.run(coal.verify(items)) == [True, True, True, False]
    finally:
        coal.close()
    assert plane.parsed_verify_calls == 1 and plane.verify_calls == 0
    assert stats[-1].decode_mode == "device"
    assert stats[-1].decode_device_lanes == 3
    assert stats[-1].decode_python_lanes == 0


def test_parsed_flush_failure_steps_decode_down_and_retries():
    """A device failure in a parsed flush steps the decode rung down to
    python PERMANENTLY and retries the SAME batch through the point
    path — without burning the process-wide msm-off rung."""
    plane = ParsedFakePlane(T, fail_parsed=1)
    coal = SlotCoalescer(plane, window=0.01, decode_workers=0,
                         decode_mode="device")
    items = _sig_items(2)
    try:
        assert asyncio.run(coal.verify(items)) == [True, True]
        assert coal._decode_live == "python"
        assert not coal._degraded  # decode rung absorbed it, not msm-off
        assert plane.parsed_verify_calls == 1
        first_point_calls = plane.verify_calls
        assert first_point_calls >= 1  # the converted retry
        # subsequent flushes decode on the python rung directly
        assert asyncio.run(coal.verify(items)) == [True, True]
        assert plane.parsed_verify_calls == 1
        assert plane.verify_calls == first_point_calls + 1
    finally:
        coal.close()


def test_stepdown_retry_applies_when_rung_already_python():
    """Double-buffered regression: a second in-flight PARSED flush can
    fail after a sibling already stepped the rung down. Applicability is
    judged by the batch (parsed lanes shipped), not the current rung —
    the retry must land here, never on the msm-off rung."""
    from charon_tpu.core.cryptoplane import _VerifyJob, _parse_verify_lane

    plane = ParsedFakePlane(T)
    coal = SlotCoalescer(plane, window=0.01, decode_workers=0,
                         decode_mode="device")
    assert coal._decode_rung() == "device"
    lanes = [_parse_verify_lane(it) for it in _sig_items(2)]

    async def drive():
        fut = asyncio.get_running_loop().create_future()
        vq = [_VerifyJob(lanes=lanes, fut=fut)]
        coal._decode_live = "python"  # sibling flush stepped down first
        return await coal._decode_stepdown_and_retry(
            vq, [], RuntimeError("injected kernel failure")
        )

    try:
        res = asyncio.run(drive())
    finally:
        coal.close()
    assert res is not None  # retried here, not passed down the ladder
    vres, rres = res
    assert vres == [[True, True]] and rres == []
    assert plane.verify_calls == 1 and not coal._degraded


def test_decode_breakdown_mode_falls_back_to_live_rung():
    """A flush whose every signature lane prefailed on host parse must
    report the rung in force, not fake a ladder step-down (the
    tpu_plane_decode_mode gauge contract)."""
    from charon_tpu.core.cryptoplane import _VerifyJob

    plane = ParsedFakePlane(T)
    coal = SlotCoalescer(plane, window=0.01, decode_workers=0,
                         decode_mode="device")
    try:
        coal._decode_live = "device"
        job = _VerifyJob(lanes=[None, None], fut=None)
        mode, cache, device, python = coal._decode_breakdown([job], [])
        assert (mode, cache, device, python) == ("device", 0, 0, 0)
    finally:
        coal.close()


def test_decompress_kernel_family_stays_on_bucket_ladder(monkeypatch):
    """The ISSUE 5 decompression kernels ride the SAME pow2 ladder as
    the flush programs: 50 random decompress_g2_batch sizes compile at
    most one program per bucket per (subgroup flag) config — growth is
    O(log max_batch), asserted by compiled-program count. Field work is
    monkeypatched to a shape-faithful fake BEFORE any trace, so the test
    is compile-free; the jit accounting is the real one."""
    import random

    import jax.numpy as jnp

    from charon_tpu.ops import blsops
    from charon_tpu.ops import decompress as DEC

    traced_shapes: list[int] = []

    def fake_dec(ctx, fr_ctx, x_raw, sign, infinity=None, host_ok=None,
                 subgroup=True):
        x0 = x_raw[0] if isinstance(x_raw, tuple) else x_raw
        traced_shapes.append(int(x0.shape[0]))
        return (x_raw, x_raw), jnp.ones(x0.shape[:-1], bool)

    monkeypatch.setattr(DEC, "decompress_g2_graph", fake_dec)
    monkeypatch.setattr(DEC, "decompress_g1_graph", fake_dec)
    blsops.clear_kernel_caches()  # rebuild wrappers over the fakes
    try:
        engine = blsops.BlsEngine()
        from charon_tpu.crypto.g1g2 import g2_to_bytes

        rng = random.Random(17)
        sizes = [rng.randrange(1, 200) for _ in range(50)]
        enc = g2_to_bytes(None)  # parse-valid infinity lane
        for n in sizes:
            pts, valid = engine.decompress_g2_batch([enc] * n)
            assert len(valid) == n
        ladder = {blsops.bucket_lanes(n) for n in sizes}
        # one compiled program per bucket, for ONE kernel config
        # (subgroup_check=True) — the trace count equals the ladder
        assert sorted(set(traced_shapes)) == sorted(ladder)
        assert len(traced_shapes) == len(ladder) <= 8
        assert blsops.jit_cache_size() == len(ladder)
        # the second config (subgroup off) adds at most one ladder more,
        # never one per flush
        for n in sizes[:20]:
            engine.decompress_g2_batch([enc] * n, subgroup_check=False)
        assert blsops.jit_cache_size() <= 2 * len(ladder)
    finally:
        blsops.clear_kernel_caches()  # drop fakes for later tests


def test_coalescer_prewarm_reports_bucket_shapes(monkeypatch):
    """SlotCoalescer.prewarm compiles the canonical duty shapes via the
    plane hook on the device lane (compile-free here: pairing faked)."""
    import jax.numpy as jnp

    from charon_tpu.ops import blsops
    from charon_tpu.ops import pairing as DP
    from charon_tpu.parallel.mesh import SlotCryptoPlane, make_mesh

    monkeypatch.setattr(
        DP, "batched_verify_rlc", lambda *a: jnp.asarray(True)
    )
    import jax

    monkeypatch.setattr(
        blsops,
        "threshold_recombine",
        # shape-faithful fake: reduce the t axis like the real fold
        lambda ctx, fr_ctx, t, sig, idx: jax.tree_util.tree_map(
            lambda a: a[:, 0], sig
        ),
    )

    def fake_grc(ctx, buckets, msg, s_total):
        return jnp.asarray(True)

    monkeypatch.setattr(DP, "grouped_rlc_check", fake_grc)
    # route _step_rlc down its non-MSM branch (batched_verify_rlc, faked
    # above) — the Straus kernels are real compiles even on tiny shapes
    from charon_tpu.ops import msm as MSM

    monkeypatch.setattr(MSM, "msm_active", lambda: False)
    monkeypatch.setattr(
        DP, "batched_verify_rlc", lambda *a: jnp.asarray(True)
    )
    monkeypatch.setattr(
        DP,
        "batched_verify",
        lambda ctx, pk, msg, sig: jnp.ones(
            __import__("jax").tree_util.tree_leaves(pk)[0].shape[0], bool
        ),
    )
    plane = SlotCryptoPlane(make_mesh(), t=T)
    coal = SlotCoalescer(plane, window=0.01)
    report = asyncio.run(
        coal.prewarm(verify_lanes=(4, 8, 17), recombine_lanes=(4,))
    )
    coal.close()
    # 4 and 8 share one bucket on the 8-device mesh -> compiled ONCE
    assert plane.bucket_lanes(4) == plane.bucket_lanes(8)
    assert [(k, n) for k, n, _ in report] == [
        ("verify", plane.bucket_lanes(4)),
        ("verify", plane.bucket_lanes(17)),
        ("recombine", plane.bucket_lanes(4)),
    ]
    # default ladder covers the SMALLEST bucket (a lone first-slot
    # submission) — lane 1 leads the canonical shapes
    assert plane.PREWARM_VERIFY_LANES[0] == 1
    # BOTH tiers compiled per distinct shape (RLC + attribution): the
    # two verify lanes share one bucket here, so 2 verify programs +
    # 2 recombine programs minimum
    assert plane.jit_cache_size() >= 4
    assert plane._verify._cache_size() >= 1
    assert plane._step._cache_size() >= 1

    # planes without a prewarm hook (test fakes) are a no-op
    bare = SlotCoalescer(FakePlane(T), window=0.01)
    assert asyncio.run(bare.prewarm()) == []
    bare.close()


# ---------------------------------------------------------------------------
# tpu_impl point caches (the decode pool's hot path)
# ---------------------------------------------------------------------------


def test_point_cache_hit_skips_redecode_and_eviction_stays_correct():
    from charon_tpu.tbls import tpu_impl

    calls = []

    def counting_decode(data: bytes):
        calls.append(data)
        return tpu_impl._decode_msg_point(data)

    cache = tpu_impl.make_point_cache(counting_decode, maxsize=2)
    a, b, c = b"\x01" * 32, b"\x02" * 32, b"\x03" * 32
    pa = cache(a)
    assert cache(a) is pa and calls == [a]  # hit path: no re-decode
    pb, pc = cache(b), cache(c)  # c evicts a (capacity 2)
    assert cache(a) == pa  # re-decoded after eviction, still correct
    assert len(calls) == 4
    assert cache(a) is not pa or calls[-1] == a


def test_point_cache_concurrent_access_race_free():
    """The module caches are hammered from the coalescer's decode pool:
    concurrent lookups of the same keys must agree and never raise.
    Duplicate decodes during a race are allowed; wrong values are not."""
    import concurrent.futures

    from charon_tpu.tbls import tpu_impl

    cache = tpu_impl.make_point_cache(tpu_impl._decode_msg_point, maxsize=8)
    keys = [i.to_bytes(32, "big") for i in range(4)]
    want = {k: tpu_impl._decode_msg_point(k) for k in keys}

    def worker(seed):
        out = []
        for i in range(12):
            k = keys[(seed + i) % len(keys)]
            out.append((k, cache(k)))
        return out

    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
        results = [
            item
            for fut in [pool.submit(worker, s) for s in range(4)]
            for item in fut.result()
        ]
    assert results and all(pt == want[k] for k, pt in results)


def test_module_caches_shared_by_coalescer_decode(monkeypatch):
    """core/cryptoplane decode routes through the tpu_impl caches: a
    second submission of the same pubkey/root never re-decodes."""
    from charon_tpu.tbls import tpu_impl

    pk_calls = []
    real = tpu_impl._decode_pubkey_point
    fresh = tpu_impl.make_point_cache(
        lambda b: (pk_calls.append(b) or real(b)), maxsize=16
    )
    monkeypatch.setattr(tpu_impl, "_cached_pubkey_point", fresh)

    items = _sig_items(2, distinct_roots=False)
    plane = SlotCoalescer(FakePlane(T), window=0.01, decode_workers=2)
    assert asyncio.run(plane.verify(items)) == [True, True]
    assert asyncio.run(plane.verify(items)) == [True, True]
    plane.close()
    assert len(pk_calls) == 1  # one pubkey, decoded exactly once


# ---------------------------------------------------------------------------
# bulk cache warm-up (ISSUE 6): PointCache.put, warm_point_caches rungs,
# the coalescer warm-up lifecycle, and the h2c kernel-family jit gate
# ---------------------------------------------------------------------------


def _fresh_caches(monkeypatch, maxsize: int = 64):
    """Swap the module point caches for empty ones so warm-up tests
    never see (or leave) state from other tests."""
    from charon_tpu.tbls import tpu_impl

    pk = tpu_impl.make_point_cache(tpu_impl._decode_pubkey_point, maxsize)
    msg = tpu_impl.make_point_cache(tpu_impl._decode_msg_point, maxsize)
    monkeypatch.setattr(tpu_impl, "_cached_pubkey_point", pk)
    monkeypatch.setattr(tpu_impl, "_cached_msg_point", msg)
    return pk, msg


def test_point_cache_bulk_put_never_decodes_and_evicts_lru():
    """put() is the warm-up entry: inserted keys hit without ever
    invoking the decoder, eviction respects maxsize in LRU order, and
    cache_info mirrors the lru_cache surface the metrics read."""
    from charon_tpu.tbls import tpu_impl

    def explode(data):  # a put key must never reach the decoder
        raise AssertionError("decode called for a warmed key")

    cache = tpu_impl.make_point_cache(explode, maxsize=2)
    cache.put(b"a", 1)
    cache.put(b"b", 2)
    assert b"a" in cache and b"b" in cache
    assert cache(b"a") == 1 and cache(b"b") == 2  # hits, no decode
    cache.put(b"c", 3)  # evicts a (LRU after the a/b hits above)
    assert b"a" not in cache and b"b" in cache and b"c" in cache
    info = cache.cache_info()
    assert (info.hits, info.misses, info.currsize, info.maxsize) == (
        2, 0, 2, 2,
    )
    cache.cache_clear()
    assert cache.cache_info().currsize == 0


def test_warm_point_caches_python_rung_idempotent(monkeypatch):
    """The python rung (device=False — the jax-less / CPU fallback)
    bulk-decodes on host, skips invalid lanes WITHOUT raising, and a
    re-warm of a superset pays only the delta."""
    from charon_tpu.tbls import tpu_impl

    pk_cache, msg_cache = _fresh_caches(monkeypatch)
    items = _sig_items(1)
    pk = items[0][0]
    stats = tpu_impl.warm_point_caches(
        pubkeys=[pk, b"\x00" * 48],  # second: flagless -> invalid
        messages=[b"root-1"],
        device=False,
    )
    assert stats["pubkey"] == {
        "device": 0, "python": 1, "cached": 0, "invalid": 1,
    }
    assert stats["message"]["python"] == 1
    assert stats["seconds"] >= 0
    assert pk in pk_cache and b"root-1" in msg_cache
    # the warmed entries are REAL decodes (spot-check vs the oracle)
    from charon_tpu.crypto import h2c

    assert msg_cache(b"root-1") == h2c.hash_to_g2(b"root-1")
    # rotation re-warm: old keys are cached, only the delta decodes
    stats2 = tpu_impl.warm_point_caches(
        pubkeys=[pk], messages=[b"root-1", b"root-2"], device=False
    )
    assert stats2["pubkey"] == {
        "device": 0, "python": 0, "cached": 1, "invalid": 0,
    }
    assert stats2["message"]["cached"] == 1
    assert stats2["message"]["python"] == 1


def test_warm_point_caches_device_engine_inserts_only_valid(monkeypatch):
    """The device rung feeds bulk-kernel outputs into the caches via
    put(); lanes the device masks invalid are NOT inserted (the
    on-demand decode re-raises the precise error later), and chunking
    splits the batch."""
    from charon_tpu.tbls import tpu_impl

    pk_cache, msg_cache = _fresh_caches(monkeypatch)
    calls = []

    class FakeEngine:
        def decompress_g1_batch(self, batch, subgroup_check=True):
            calls.append(("g1", list(batch)))
            return [f"pt-{b.hex()[:4]}" for b in batch], [
                b[0] != 0xFF for b in batch
            ]

        def hash_to_g2_batch(self, batch):
            calls.append(("h2c", list(batch)))
            return [f"h2c-{b.hex()[:4]}" for b in batch], [True] * len(batch)

    keys = [bytes([i]) * 48 for i in (1, 2, 0xFF)]
    msgs = [bytes([i]) * 32 for i in (5, 6, 7)]
    stats = tpu_impl.warm_point_caches(
        pubkeys=keys, messages=msgs, engine=FakeEngine(), device=True,
        chunk=2,
    )
    assert stats["pubkey"] == {
        "device": 2, "python": 0, "cached": 0, "invalid": 1,
    }
    assert stats["message"]["device"] == 3
    assert [kind for kind, _ in calls] == ["g1", "g1", "h2c", "h2c"]
    assert keys[0] in pk_cache and keys[1] in pk_cache
    assert keys[2] not in pk_cache  # invalid lane never inserted
    assert all(m in msg_cache for m in msgs)


def test_warm_point_caches_caps_at_capacity_reports_overflow(monkeypatch):
    """A key set past the cache capacity warms only the LAST cap keys
    (the ones that survive insertion order) and reports the rest as
    overflow — no device/host work burned on lanes eviction would
    discard, no 'warmed' claim for keys that are not."""
    from charon_tpu.tbls import tpu_impl

    cache = tpu_impl.make_point_cache(tpu_impl._decode_msg_point, 2)
    monkeypatch.setattr(tpu_impl, "_cached_msg_point", cache)
    msgs = [b"m%d" % i for i in range(5)]
    stats = tpu_impl.warm_point_caches(messages=msgs, device=False)
    assert stats["message"]["python"] == 2
    assert stats["message"]["overflow"] == 3
    assert msgs[-1] in cache and msgs[-2] in cache
    assert all(m not in cache for m in msgs[:3])


def test_warm_point_caches_device_failure_steps_down_not_raises(monkeypatch):
    """A device failure mid-pass steps the REST of the warm-up down to
    the python rung (PR 2 ladder) — a failing device can degrade a
    rotation warm, never abort it."""
    from charon_tpu.tbls import tpu_impl

    _, msg_cache = _fresh_caches(monkeypatch)

    class DyingEngine:
        def hash_to_g2_batch(self, batch):
            raise RuntimeError("injected device failure")

        decompress_g1_batch = hash_to_g2_batch

    msgs = [b"a" * 32, b"b" * 32, b"c" * 32]
    stats = tpu_impl.warm_point_caches(
        messages=msgs, engine=DyingEngine(), device=True, chunk=2
    )
    # first chunk hit the failure and stepped down; EVERY lane still
    # warmed on host (the failed chunk retries on the python rung)
    assert stats["message"] == {
        "device": 0, "python": 3, "cached": 0, "invalid": 0,
    }
    assert all(m in msg_cache for m in msgs)


class WarmFakePlane(ParsedFakePlane):
    """ParsedFakePlane + the sharded warm-program host APIs, recording
    which thread drove them (the warm-up must never ride the serialized
    device lane) and holding the device lane busy on demand."""

    def __init__(self, t: int, verify_sleep: float = 0.0):
        super().__init__(t)
        self.verify_sleep = verify_sleep
        self.flush_started = threading.Event()
        self.warm_calls: list[tuple[str, int, str]] = []

    def verify_packed_parsed(self, arrays, rand, n: int):
        self.flush_started.set()
        if self.verify_sleep:
            time.sleep(self.verify_sleep)
        return super().verify_packed_parsed(arrays, rand, n)

    def decompress_g1_host(self, encoded):
        from charon_tpu.crypto import g1g2

        self.warm_calls.append(
            ("g1", len(encoded), threading.current_thread().name)
        )
        pts, valid = [], []
        for enc in encoded:
            try:
                pts.append(g1g2.g1_from_bytes(bytes(enc)))
                valid.append(True)
            except ValueError:
                pts.append(None)
                valid.append(False)
        return pts, valid

    def hash_to_g2_host(self, msgs):
        from charon_tpu.crypto import h2c

        self.warm_calls.append(
            ("h2c", len(msgs), threading.current_thread().name)
        )
        return [h2c.hash_to_g2(bytes(m)) for m in msgs], [True] * len(msgs)


def test_warm_caches_device_rung_rotation_rewarm(monkeypatch):
    """The coalescer warm-up lifecycle: a warm pass decodes through the
    plane's warm programs on a dedicated worker thread, feeds the
    module caches, fires warmup_hook; a rotation re-warm pays only the
    delta; and the warm-up lanes land in the new metric families."""
    from charon_tpu.app.metrics import ClusterMetrics
    from charon_tpu.crypto import h2c

    pk_cache, msg_cache = _fresh_caches(monkeypatch)
    items = _sig_items(1)
    pk = items[0][0]
    plane = WarmFakePlane(T)
    metrics = ClusterMetrics("0xhash", "c", "node0")
    coal = SlotCoalescer(plane, window=0.01, decode_workers=0,
                         decode_mode="device")
    coal.warmup_hook = metrics.observe_warmup
    try:
        stats = asyncio.run(
            coal.warm_caches(pubkeys=[pk], messages=[b"slot-root-1"])
        )
        assert stats["pubkey"]["device"] == 1
        assert stats["message"]["device"] == 1
        assert pk in pk_cache
        assert msg_cache(b"slot-root-1") == h2c.hash_to_g2(b"slot-root-1")
        # every warm call ran on the dedicated warm-up thread
        assert plane.warm_calls and all(
            name.startswith("crypto-warmup") for _, _, name in plane.warm_calls
        )
        # rotation: superset re-warm decodes ONLY the new message
        stats2 = asyncio.run(
            coal.warm_caches(
                pubkeys=[pk], messages=[b"slot-root-1", b"slot-root-2"]
            )
        )
        assert stats2["pubkey"] == {
            "device": 0, "python": 0, "cached": 1, "invalid": 0,
        }
        assert stats2["message"]["device"] == 1
        assert stats2["message"]["cached"] == 1
        assert coal.warmups == 2 and coal.warmup_lanes == 3
    finally:
        coal.close()
    out = metrics.render().decode()
    assert 'tpu_point_cache_warmup_lanes_total{cache="pubkey"' in out
    assert 'source="device"' in out and 'source="cached"' in out
    assert "tpu_point_cache_warmup_seconds_count" in out


def test_warm_caches_does_not_serialize_behind_live_flush(monkeypatch):
    """A warm-up racing a live flush must complete while the device
    lane is still busy — it owns its own thread, never queues behind
    the serialized flush lane (the rotation-before-next-slot
    contract)."""
    _fresh_caches(monkeypatch)
    items = _sig_items(2)
    plane = WarmFakePlane(T, verify_sleep=0.8)
    coal = SlotCoalescer(plane, window=0.01, decode_workers=0,
                         decode_mode="device")

    async def main():
        flush = asyncio.create_task(coal.verify(items))
        await asyncio.get_running_loop().run_in_executor(
            None, plane.flush_started.wait, 5.0
        )
        t0 = time.monotonic()
        stats = await coal.warm_caches(messages=[b"rotation-root"])
        warm_elapsed = time.monotonic() - t0
        assert not flush.done(), "device flush finished before warm-up?"
        res = await flush
        return stats, warm_elapsed, res

    try:
        stats, warm_elapsed, res = asyncio.run(main())
    finally:
        coal.close()
    assert res == [True, True]
    assert stats["message"]["device"] == 1
    assert warm_elapsed < 0.6, (
        f"warm-up serialized behind the live flush ({warm_elapsed:.2f}s)"
    )


def test_warm_caches_python_rung_when_plane_lacks_warm_api(monkeypatch):
    """Planes without the warm programs (python decode rung, test
    fakes) fall back to the host bigint warm — still off the loop,
    still feeding the caches."""
    _fresh_caches(monkeypatch)
    coal = SlotCoalescer(ParsedFakePlane(T), window=0.01,
                         decode_workers=0, decode_mode="device")
    try:
        stats = asyncio.run(coal.warm_caches(messages=[b"cold-root"]))
    finally:
        coal.close()
    assert stats["message"]["python"] == 1
    assert stats["message"]["device"] == 0


def test_warm_caches_jaxless_host_reports_skip(monkeypatch):
    """On a host where the tbls device backend cannot import (no jax),
    warm_caches reports the skip instead of failing startup."""
    import sys

    import charon_tpu.tbls as tbls_pkg

    monkeypatch.setitem(sys.modules, "charon_tpu.tbls.tpu_impl", None)
    monkeypatch.delattr(tbls_pkg, "tpu_impl", raising=False)
    coal = SlotCoalescer(WarmFakePlane(T), window=0.01,
                         decode_workers=0, decode_mode="device")
    try:
        stats = asyncio.run(
            coal.warm_caches(pubkeys=[b"\x01" * 48], messages=[b"m"])
        )
    finally:
        coal.close()
    assert stats["pubkey"] == {"skipped": 1}
    assert stats["message"] == {"skipped": 1}


def test_node_rewarm_hook_routes_to_plane(monkeypatch):
    """Node.rewarm_point_caches (the validator-set rotation hook) rides
    the coalescer warm path when a crypto plane is installed."""
    from charon_tpu.app.metrics import ClusterMetrics

    # app.run pulls the p2p identity stack; hosts without the optional
    # `cryptography` wheel still cover the coalescer-level warm path
    # in the tests above
    run_mod = pytest.importorskip("charon_tpu.app.run")
    Node = run_mod.Node

    _fresh_caches(monkeypatch)
    plane = WarmFakePlane(T)
    coal = SlotCoalescer(plane, window=0.01, decode_workers=0,
                         decode_mode="device")
    node = Node(
        config=None, lock=None, life=None, scheduler=None, vapi=None,
        vapi_router=None, p2p=None, bcast=None, tracker=None,
        metrics=ClusterMetrics("0x", "c", "n0"), beacon=None,
        crypto_plane=coal,
    )
    try:
        stats = asyncio.run(node.rewarm_point_caches(messages=[b"rot"]))
    finally:
        coal.close()
    assert stats["message"]["device"] == 1
    assert [k for k, _, _ in plane.warm_calls] == ["h2c"]


def test_h2c_kernel_family_stays_on_bucket_ladder(monkeypatch):
    """The ISSUE 6 hash-to-curve kernels ride the SAME pow2 ladder as
    every other family: 50 random hash_to_g2_batch sizes compile at
    most one program per bucket (field work monkeypatched to a
    shape-faithful fake BEFORE any trace — compile-free; the jit
    accounting is the real one)."""
    import random

    import jax.numpy as jnp

    from charon_tpu.ops import blsops
    from charon_tpu.ops import sswu as SSWU

    traced_shapes: list[int] = []

    def fake_h2c(ctx, fr_ctx, u0, u1, s0, s1, host_ok=None):
        traced_shapes.append(int(u0[0].shape[0]))
        return (u0, u0), jnp.ones(u0[0].shape[:-1], bool)

    monkeypatch.setattr(SSWU, "hash_to_g2_graph", fake_h2c)
    blsops.clear_kernel_caches()  # rebuild wrappers over the fake
    try:
        engine = blsops.BlsEngine()
        lane = SSWU.hash_to_field_lane(b"ladder-probe")
        rng = random.Random(23)
        sizes = [rng.randrange(1, 200) for _ in range(50)]
        for n in sizes:
            pts, valid = engine.hash_to_g2_batch([lane] * n)
            assert len(valid) == n
        ladder = {blsops.bucket_lanes(n) for n in sizes}
        assert sorted(set(traced_shapes)) == sorted(ladder)
        assert len(traced_shapes) == len(ladder) <= 8
        assert blsops.jit_cache_size() == len(ladder)
    finally:
        blsops.clear_kernel_caches()  # drop the fake for later tests


# ---------------------------------------------------------------------------
# multi-tenant crypto-plane service (ISSUE 8): backpressure, fairness,
# breaker, and the degradation ladder consuming shed load
# ---------------------------------------------------------------------------

from charon_tpu.core.cryptosvc import (  # noqa: E402
    CryptoPlaneService,
    PlaneOverloadError,
    TenantQuota,
)


class StubCoalescer:
    """Service-level stand-in for the shared SlotCoalescer: records
    dispatch order (the EDF observable), optionally holds the 'device'
    for delay seconds, and verdicts each lane by its truthiness —
    items submitted as 0/None fail verification, everything else
    passes (the forged-flood signal without any crypto)."""

    def __init__(self, t: int = T, delay: float = 0.0):
        self.t = t
        self.delay = delay
        self.calls: list[tuple[str, str | None, int]] = []

    async def verify(self, items, deadline=None, tenant=None):
        self.calls.append(("verify", tenant, len(items)))
        if self.delay:
            await asyncio.sleep(self.delay)
        return [bool(it) for it in items]

    async def recombine(
        self, pubshares, roots, partials, group_pks, indices,
        deadline=None, tenant=None,
    ):
        self.calls.append(("recombine", tenant, len(roots)))
        if self.delay:
            await asyncio.sleep(self.delay)
        return [b"\x01" * 96] * len(roots), [True] * len(roots)


def test_overload_fails_fast_never_blocks_the_loop():
    """Submissions beyond the tenant's queue bounds raise the typed
    PlaneOverloadError IMMEDIATELY (no await between check and raise),
    while in-flight work completes normally; shed counters attribute
    the rejections."""
    stub = StubCoalescer(delay=0.2)
    svc = CryptoPlaneService(stub, round_interval=0.001)
    plane = svc.register(
        "a", TenantQuota(max_queue_jobs=2, max_queue_lanes=100)
    )

    async def main():
        first = asyncio.create_task(plane.verify([1]))
        second = asyncio.create_task(plane.verify([1, 1]))
        await asyncio.sleep(0.05)  # both dispatched, device busy
        t0 = time.monotonic()
        with pytest.raises(PlaneOverloadError) as exc:
            await plane.verify([1])
        elapsed = time.monotonic() - t0
        assert elapsed < 0.1, "overload must fail fast, not queue"
        assert exc.value.tenant == "a" and exc.value.reason == "jobs"
        # lane bound sheds too (jobs bound not yet hit after drain)
        assert await first == [True]
        assert await second == [True, True]
        with pytest.raises(PlaneOverloadError) as exc2:
            await plane.verify([1] * 101)
        assert exc2.value.reason == "lanes"

    asyncio.run(main())
    ten = svc.tenant("a")
    assert ten.shed == {"jobs": 1, "lanes": 1}
    assert ten.shed_lanes == 1 + 101
    svc.close()


def test_edf_preempts_flooder_backlog():
    """A starved tenant's near-deadline duty dispatches ahead of a
    flooder's queued no-deadline backlog: earliest-deadline-first
    across tenants, within per-tenant round budgets."""
    stub = StubCoalescer()
    svc = CryptoPlaneService(stub, round_lanes=8, round_interval=0.03)
    flood = svc.register("flood", TenantQuota(max_queue_lanes=10_000))
    victim = svc.register("victim", TenantQuota())

    async def main():
        # budget/round = 8 * 1/2 = 4 lanes: one 4-lane entry per round
        flood_tasks = [
            asyncio.create_task(flood.verify([1] * 4)) for _ in range(3)
        ]
        await asyncio.sleep(0.005)  # round 1 dispatched one flood entry
        res = await victim.verify([1] * 4, deadline=time.time() + 0.05)
        assert res == [True] * 4
        await asyncio.gather(*flood_tasks)

    asyncio.run(main())
    order = [tenant for _, tenant, _ in stub.calls]
    assert order[0] == "flood"
    # the victim preempted the flooder's remaining backlog
    assert order.index("victim") < len(order) - 1
    assert order.count("flood") == 3 and order.count("victim") == 1
    svc.close()


def test_breaker_open_quarantine_half_open_close():
    """Forged-flood breaker lifecycle: persistent failed lanes open the
    breaker (subsequent dispatches quarantine to the tenant's own
    coalescer), the cooldown half-opens it, one clean quarantined
    flush closes it — and a failing probe re-opens instead."""
    shared = StubCoalescer()
    quarantine = StubCoalescer()
    transitions: list[tuple[str, str]] = []

    def observer(kind, tenant, **f):
        if kind == "breaker":
            transitions.append((tenant, f["state"]))

    svc = CryptoPlaneService(
        shared,
        round_interval=0.001,
        observer=observer,
        quarantine_factory=lambda tid: quarantine,
    )
    plane = svc.register(
        "evil",
        TenantQuota(
            breaker_window=64,
            breaker_min_lanes=8,
            breaker_threshold=0.5,
            breaker_cooldown=0.05,
        ),
    )

    async def main():
        # two clean flushes first: the window must TRIP on ratio, not
        # on the first failure
        assert await plane.verify([1, 1]) == [True, True]
        assert svc.tenant("evil").breaker.state == "closed"
        # forged flood: 8 failing lanes >= min_lanes at ratio >= 0.5
        await plane.verify([0] * 8)
        assert svc.tenant("evil").breaker.state == "open"
        before = len(shared.calls)
        # open: dispatches quarantine to the tenant's own coalescer
        await plane.verify([0] * 4)
        assert len(shared.calls) == before
        assert quarantine.calls[-1] == ("verify", "evil", 4)
        assert svc.tenant("evil").quarantined_flushes == 1
        # cooldown elapses -> half-open; a failing probe re-opens
        await asyncio.sleep(0.06)
        await plane.verify([0, 1])
        assert svc.tenant("evil").breaker.state == "open"
        # cooldown again -> half-open; a CLEAN probe closes
        await asyncio.sleep(0.06)
        await plane.verify([1, 1])
        assert svc.tenant("evil").breaker.state == "closed"
        # closed again: back to the shared coalescer
        await plane.verify([1])
        assert shared.calls[-1] == ("verify", "evil", 1)

    asyncio.run(main())
    states = [s for _, s in transitions]
    assert states == ["open", "half_open", "open", "half_open", "closed"]
    svc.close()


def test_shed_load_consumed_by_degradation_ladder():
    """The submitters' existing ladders CATCH PlaneOverloadError and
    serve shed work from the host tbls rung: Eth2Verifier inbound sets
    still verify, SigAgg still aggregates — shed costs latency, never
    a duty."""
    from charon_tpu import tbls
    from charon_tpu.core.parsigex import Eth2Verifier
    from charon_tpu.core.sigagg import SigAgg
    from tests.test_cryptoplane import FORK, _duty_workload
    from charon_tpu.core.types import Duty, DutyType

    impl = PythonImpl()
    tbls.set_implementation(impl)
    stub = StubCoalescer()
    svc = CryptoPlaneService(stub, round_interval=0.001)
    # zero-depth quota: EVERY submission sheds at admission
    plane = svc.register("a", TenantQuota(max_queue_jobs=0))

    pk, gpk, psigs, root, want, pubshares = _duty_workload(impl, slot=3)
    pubshares_by_idx = {i: {pk: pubshares[i]} for i in pubshares}
    duty = Duty(3, DutyType.ATTESTER)

    async def main():
        verifier = Eth2Verifier(FORK, pubshares_by_idx, plane=plane)
        signed_set = {pk: psigs[0]}
        assert await verifier.verify_async(duty, signed_set) is True

        agg = SigAgg(
            threshold=T,
            fork=FORK,
            plane=plane,
            pubshares_by_idx=pubshares_by_idx,
        )
        out: dict = {}

        async def sub(_duty, result):
            out.update(result)

        agg.subscribe(sub)
        await agg.aggregate(duty, {pk: psigs})
        assert out[pk].signature == want

    asyncio.run(main())
    # the plane never saw the work; the shed counters name the tenant
    assert stub.calls == []
    assert svc.tenant("a").shed.get("jobs", 0) == 2
    svc.close()


def test_cancelled_submission_dropped_not_dispatched():
    """A tenant crash-loop cancels submissions mid-queue: the dead
    entries are dropped at dispatch (never shipped, never wedge the
    queue) and their pending accounting is released."""
    stub = StubCoalescer(delay=0.05)
    svc = CryptoPlaneService(stub, round_interval=0.01)
    plane = svc.register("crashy", TenantQuota())

    async def main():
        hold = asyncio.create_task(plane.verify([1]))  # occupies device
        await asyncio.sleep(0.005)
        doomed = [
            asyncio.create_task(plane.verify([1] * 2)) for _ in range(4)
        ]
        await asyncio.sleep(0)  # enqueue, then crash before dispatch
        for task in doomed:
            task.cancel()
        await asyncio.gather(*doomed, return_exceptions=True)
        assert await hold == [True]
        # survivor submitted after the crash still round-trips
        assert await plane.verify([1, 1]) == [True, True]

    asyncio.run(main())
    ten = svc.tenant("crashy")
    assert ten.pending_jobs == 0 and ten.pending_lanes == 0
    # none of the cancelled entries reached the coalescer
    assert sum(n for _, _, n in stub.calls) == 3
    svc.close()


def test_flush_stats_carry_tenant_lanes():
    """Tenant tags travel submission -> coalescer job -> FlushStats:
    the per-flush attribution the tenant metrics and span-bridge tenant
    attrs are built from."""
    stats: list = []
    coal = SlotCoalescer(
        FakePlane(T), window=0.01, stats_hook=stats.append
    )
    svc = CryptoPlaneService(coal, round_interval=0.001)
    a = svc.register("tenant-a", TenantQuota())
    b = svc.register("tenant-b", TenantQuota())

    async def main():
        items = _sig_items(2)
        await asyncio.gather(a.verify(items), b.verify(items[:1]))

    asyncio.run(main())
    svc.close()
    coal.close()
    per: dict[str, int] = {}
    for s in stats:
        for tenant, lanes in s.tenant_lanes:
            per[tenant] = per.get(tenant, 0) + lanes
    assert per == {"tenant-a": 2, "tenant-b": 1}


def test_clock_step_does_not_collapse_armed_window():
    """Regression (ISSUE 8 satellite): the wall->monotonic offset is
    snapshotted ONCE per window, so a host clock step between two
    submissions of the same window no longer shrinks or stretches the
    armed flush — same wall deadline, same monotonic flush state."""
    from charon_tpu.testutil.chaos import SkewedClock

    coal = SlotCoalescer(FakePlane(T), window=0.5, window_min=0.001)

    async def main():
        with SkewedClock() as clock:
            deadline = time.time() + 30.0
            coal._arm(deadline)
            (timer,) = coal._timers.values()  # one kind in the window
            armed_at = coal._flush_at
            queue_deadline = timer.queue_deadline
            clock.step(3600.0)  # host clock jumps forward an hour
            coal._arm(deadline)
            # pre-fix: deadline - time.time() went negative, the cap
            # collapsed to window_min and the armed flush fired NOW
            assert timer.queue_deadline == queue_deadline
            assert coal._flush_at == armed_at
            clock.step(-7200.0)  # and an hour backward past real time
            coal._arm(deadline)
            assert timer.queue_deadline == queue_deadline
            assert coal._flush_at == armed_at
        coal._flush_task.cancel()

    asyncio.run(main())
    coal.close()
