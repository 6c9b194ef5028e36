"""TPU tbls backend: batched JAX kernels behind the Implementation API.

Where the reference binds herumi's C++ one-call-per-signature backend
(ref: tbls/herumi.go), this backend routes every operation through the
batched device engine (charon_tpu/ops/blsops.py). Single-item calls are
batches of one; the core workflow uses the *_batch entry points to push
whole duty-sets through one compiled XLA program per slot.

Host/device split (SURVEY.md §7 design stance):
  * secret material (keygen, Shamir split/recover, signing) stays on the
    host — the device only ever sees public points;
  * hash-to-curve (SHA-256 expand + SSWU) runs on the host, cached;
  * pairings, Lagrange recombination, point sums, and subgroup checks run
    batched on the device.

Caching: decompressed pubkeys are cached by compressed bytes (cluster
pubshares are a small static set — ref: core/validatorapi pubshare maps),
as are hashed messages.
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Mapping, NamedTuple, Sequence

from charon_tpu.crypto import g1g2, h2c
from charon_tpu.crypto.fields import R
from charon_tpu.ops import blsops
from charon_tpu.ops import curve as C
from charon_tpu.ops import limb
from charon_tpu.tbls import Implementation, TblsError
from charon_tpu.tbls.python_impl import PythonImpl, sig_to_point


def _decode_pubkey_point(pubkey: bytes):
    """Decompress + subgroup-check a pubkey (uncached decode body)."""
    try:
        pt = g1g2.g1_from_bytes(pubkey, subgroup_check=True)
    except ValueError as e:
        raise TblsError(str(e)) from e
    if pt is None:
        raise TblsError("infinite public key")
    return pt


class MsgHashEngine:
    """Hashes a message to G2 on a miss of the message cache.

    Two engines compute the one function (RFC 9380, the same affine
    point bit for bit): `native` — `ctpu_hash_to_g2` of
    native/libcharon_native.so through ctypes, which drops the GIL for
    the call, then the python rung's own G2 decompression of the 96
    bytes (no subgroup check: a hash-to-curve output is in the subgroup
    by construction) — and `python`, crypto/h2c in bigints, ~14 ms a
    root with the GIL held. Which one serves is observed, not set: on
    the first miss, under the lock, the library is loaded
    (tbls/native_impl, `CHARON_NATIVE_LIB` as ever) and one fixed
    message is hashed by both. Library or symbol missing, or answers
    that differ: python, for the life of the process. That guarded
    first call is also what makes the decode pool safe — the library's
    `ensure_init()` is an unguarded `if (!INITED)`, and pool threads
    must not be the first to enter it.

    Counts every hash by engine (`counts`, the
    `tpu_point_cache_message_hashed` family) and per calling thread
    (`on_thread`: what a decode chunk reads before and after its lanes
    to put `msg_hashed` on its `cryptoplane.decode` span)."""

    PROBE = b"charon-tpu message hash engine probe"

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._hash = None  # resolved on the first miss
        self.name: str | None = None  # "native" | "python" once resolved
        self._counts = {"native": 0, "python": 0}
        self._here = threading.local()

    @staticmethod
    def _native_hasher():
        """The native engine's hasher, or None where the process has no
        library that agrees with the specification code."""
        try:
            from charon_tpu.tbls.native_impl import NativeImpl
        except (ImportError, OSError, AttributeError):
            return None  # not built / not loadable / symbol missing
        native = NativeImpl()

        def hash_native(data: bytes):
            return sig_to_point(
                native.hash_to_g2_bytes(data), subgroup_check=False
            )

        try:
            agrees = hash_native(MsgHashEngine.PROBE) == h2c.hash_to_g2(
                MsgHashEngine.PROBE
            )
        except TblsError:
            agrees = False
        return hash_native if agrees else None

    def __call__(self, data: bytes):
        if self._hash is None:
            with self._lock:
                if self._hash is None:
                    native = self._native_hasher()
                    self.name = "native" if native else "python"
                    self._hash = native or h2c.hash_to_g2
        pt = self._hash(data)
        with self._lock:
            self._counts[self.name] += 1
        self._here.n = self.on_thread() + 1
        return pt

    def counts(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def on_thread(self) -> int:
        """Messages hashed so far on the calling thread."""
        return getattr(self._here, "n", 0)


# the decoder behind _cached_msg_point (and the python rung of
# warm_point_caches); process-wide, as the library and the caches are
_decode_msg_point = MsgHashEngine()


class _CacheInfo(NamedTuple):
    hits: int
    misses: int
    maxsize: int
    currsize: int


class PointCache:
    """Thread-safe LRU point cache with BULK insertion (ISSUE 6).

    functools.lru_cache almost fits, but it cannot be pre-populated —
    and the whole point of the warm-up path is to decode a restart's
    key/message set through ONE device program and insert the results,
    so the first live slot starts at a ~100% hit rate instead of
    paying a python-bigint burst. Mirrors the lru_cache surface the
    metrics/test plumbing reads (cache_info / cache_clear) plus put()
    and __contains__ for the bulk path. Decode runs OUTSIDE the lock:
    the caches are hammered from the coalescer's decode pool, so
    concurrent misses of the same key may decode twice (same contract
    as lru_cache) but never block each other for milliseconds."""

    def __init__(self, decode, maxsize: int):
        self._decode = decode
        self._maxsize = maxsize
        self._data: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0

    def __call__(self, key):
        with self._lock:
            try:
                val = self._data[key]
            except KeyError:
                self._misses += 1
            else:
                self._data.move_to_end(key)
                self._hits += 1
                return val
        val = self._decode(key)  # bigint work — never under the lock
        self.put(key, val)
        return val

    def put(self, key, value) -> None:
        """Insert without decoding — the bulk warm-up entry point."""
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            while len(self._data) > self._maxsize:
                self._data.popitem(last=False)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._data

    def cache_info(self) -> _CacheInfo:
        with self._lock:
            return _CacheInfo(
                self._hits, self._misses, self._maxsize, len(self._data)
            )

    def cache_clear(self) -> None:
        with self._lock:
            self._data.clear()
            self._hits = 0
            self._misses = 0


def make_point_cache(decode, maxsize: int) -> PointCache:
    """LRU-wrap a point decoder. The module-level caches below use the
    production capacities; tests build small-capacity instances of the
    SAME wrapper to pin hit/eviction/concurrency/bulk-put behavior
    (the caches are hammered from the coalescer's decode pool, so
    PointCache's thread-safety is load-bearing)."""
    return PointCache(decode, maxsize)


# Decompressed pubkeys cached by compressed bytes (cluster pubshares are
# a small static set — ref: core/validatorapi pubshare maps), as are
# hashed messages. Shared by this impl AND core/cryptoplane's decode
# pool, and bulk-fed by the warm-up path below. The pubkeys are warmed
# at boot; the messages cannot be — a signing root does not exist
# before its slot — so the first job of every wave misses once per
# distinct root (31-32 an attester wave); a job that arrives while
# those are still being hashed misses them again (concurrent misses of
# one key decode twice: PointCache), every later one hits. A miss costs
# one MsgHashEngine call: ~3.5 ms of native code with the GIL released
# plus ~2.5 ms of Python decompression on a host that has the library,
# ~14 ms of GIL-held bigints on one that has not.
_cached_pubkey_point = make_point_cache(_decode_pubkey_point, 65536)
_cached_msg_point = make_point_cache(_decode_msg_point, 16384)

# Warm-up lanes per device program — THE default for every warm path
# (SlotCoalescer.warm_caches inherits it; docs/operations.md documents
# it): big enough to amortize dispatch, small enough that a warm chunk
# never monopolizes the device for whole seconds.
WARMUP_CHUNK = 512


def warm_point_caches(
    pubkeys: Sequence[bytes] = (),
    messages: Sequence[bytes] = (),
    engine: "blsops.BlsEngine | None" = None,
    device: bool | None = None,
    chunk: int = WARMUP_CHUNK,
) -> dict:
    """Bulk-populate the module point caches (ISSUE 6 cold path).

    Pubkeys decode through `decompress_g1_batch` (GLV subgroup check)
    and messages through `hash_to_g2_batch` (device SSWU + isogeny +
    psi cofactor clearing) in `chunk`-sized device programs; the
    python rung (`device=False`, or auto on a non-TPU backend) decodes
    per point on host — still a valid warm-up, just the old cost.
    Lanes the device marks invalid are NOT inserted: the on-demand
    decode re-raises the precise error when (if ever) the key is used.

    A device failure mid-pass (lost device, XLA runtime error) steps
    the REST of the pass down to the python rung instead of raising —
    the PR 2 ladder discipline; warm-up can degrade but never aborts a
    rotation, and the step-down is visible as python lanes in the
    stats.

    Returns per-cache stats: lanes by source (device/python/cached/
    invalid) plus wall seconds — the shape app/metrics.observe_warmup
    records."""
    import time as _time

    t0 = _time.monotonic()
    if device is None:
        device = limb._is_tpu_backend()
    eng = None
    if device:
        try:
            eng = engine or blsops.default_engine()
        except Exception:  # jax-less / broken backend: host rung
            device = False
    rung = {"device": device}
    stats = {
        "pubkey": {"device": 0, "python": 0, "cached": 0, "invalid": 0},
        "message": {"device": 0, "python": 0, "cached": 0, "invalid": 0},
    }

    def work(keys, cache, bulk, single, name):
        st = stats[name]
        # lanes are UNIQUE keys: duplicates in the input collapse before
        # accounting, so a cold start with a repeated key never reports
        # source="cached" lanes it did not actually skip
        uniq = list(dict.fromkeys(keys))
        todo = [k for k in uniq if k not in cache]
        st["cached"] += len(uniq) - len(todo)
        cap = cache.cache_info().maxsize
        if len(todo) > cap:
            # decoding past capacity would only evict its own results:
            # warm the LAST cap keys (insertion order keeps them alive)
            # and report the rest as overflow — never burn device work
            # on lanes that cannot survive, never report them "warmed"
            st["overflow"] = st.get("overflow", 0) + len(todo) - cap
            todo = todo[-cap:]
        for i in range(0, len(todo), chunk):
            batch = todo[i : i + chunk]
            if rung["device"]:
                try:
                    pts, valid = bulk(batch)
                except Exception:  # noqa: BLE001 — device rung failure
                    # (lost device / XLA error): step the rest of the
                    # pass down to host decode, never raise out of a
                    # warm-up
                    rung["device"] = False
                else:
                    for k, pt, ok in zip(batch, pts, valid):
                        if ok and pt is not None:
                            cache.put(k, pt)
                            st["device"] += 1
                        else:
                            st["invalid"] += 1
                    continue
            for k in batch:
                try:
                    cache.put(k, single(k))
                    st["python"] += 1
                except (TblsError, ValueError):
                    st["invalid"] += 1

    work(
        pubkeys,
        _cached_pubkey_point,
        lambda b: eng.decompress_g1_batch(b, subgroup_check=True),
        _decode_pubkey_point,
        "pubkey",
    )
    work(
        messages,
        _cached_msg_point,
        lambda b: eng.hash_to_g2_batch(b),
        _decode_msg_point,
        "message",
    )
    stats["seconds"] = _time.monotonic() - t0
    return stats


class TPUImpl(Implementation):
    """Batched device implementation.

    verify_inputs: when True (default), signature points are
    subgroup-checked on device before use. The core workflow's aggregation
    path sets False because every partial signature it aggregates was
    already individually verified on arrival (ref: core/parsigex
    verification before store).
    """

    def __init__(
        self,
        engine: blsops.BlsEngine | None = None,
        verify_inputs: bool = True,
        decode_mode: str = "auto",
    ):
        self.engine = engine or blsops.default_engine()
        self.verify_inputs = verify_inputs
        # signature decompression routing (ISSUE 5): "device" batches the
        # Fp2 sqrt + sign + psi subgroup check into one kernel (folding
        # the separate subgroup_check_g2_batch dispatch), "python" keeps
        # the host bigint path, "auto" = device on TPU backends only —
        # the python rung stays the degradation floor below it.
        if decode_mode not in ("auto", "device", "python"):
            raise ValueError(f"bad decode_mode {decode_mode!r}")
        self.decode_mode = decode_mode
        self._host = PythonImpl()
        # degradation ladder for device failures in the RLC batch path
        # (mirrors bench.py): Pippenger MSM off first (the newest kernel
        # family), then fused-fp2 off, then RLC off entirely
        self._degrade_rungs = ["msm-off", "fp2-fusion-off"]

    # -- host-side secret ops (delegate to the Python backend) ------------

    def generate_secret_key(self) -> bytes:
        return self._host.generate_secret_key()

    def secret_to_public_key(self, secret: bytes) -> bytes:
        return self._host.secret_to_public_key(secret)

    def threshold_split(self, secret: bytes, total: int, threshold: int):
        return self._host.threshold_split(secret, total, threshold)

    def recover_secret(self, shares, total: int, threshold: int) -> bytes:
        return self._host.recover_secret(shares, total, threshold)

    def sign(self, secret: bytes, data: bytes) -> bytes:
        return self._host.sign(secret, data)

    # -- decompression helpers -------------------------------------------

    def _device_decode(self) -> bool:
        if self.decode_mode != "auto":
            return self.decode_mode == "device"
        return limb._is_tpu_backend()

    def _sig_points(self, sigs: Sequence[bytes], what: str) -> list:
        """Decompress signatures — the bulk path runs the whole decode
        (sqrt + sign + on-curve + subgroup) as ONE device program
        (ops/decompress.py); the python rung decompresses on host and
        pays a separate subgroup dispatch when verify_inputs is set."""
        if self._device_decode():
            pts, valid = self.engine.decompress_g2_batch(
                sigs, subgroup_check=self.verify_inputs
            )
            for pt, ok in zip(pts, valid):
                if not ok:
                    raise TblsError(
                        f"{what} failed decompression or subgroup check"
                    )
                if pt is None:
                    raise TblsError(f"infinite {what}")
            return pts
        pts = []
        for sig in sigs:
            pt = sig_to_point(sig, subgroup_check=False)
            if pt is None:
                raise TblsError(f"infinite {what}")
            pts.append(pt)
        if self.verify_inputs:
            ok = self.engine.subgroup_check_g2_batch(pts)
            if not all(ok):
                raise TblsError(f"{what} not in G2 subgroup")
        return pts

    # -- verification -----------------------------------------------------

    def verify(self, pubkey: bytes, data: bytes, sig: bytes) -> None:
        if not self.verify_batch([(pubkey, data, sig)])[0]:
            raise TblsError("signature verification failed")

    # Below this size the per-lane kernel is used directly: RLC's shared
    # tail amortizes only over larger batches, and small shapes would
    # compile a second kernel family for no win.
    RLC_MIN_BATCH = 16

    def verify_batch(self, items) -> list[bool]:
        if not items:
            return []
        n = len(items)
        pks: list = [None] * n
        msgs: list = [None] * n
        sigs: list = [None] * n
        ok = [True] * n
        device_decode = self._device_decode()
        if device_decode:
            # one device program decompresses AND subgroup-checks every
            # signature lane — the separate subgroup_check_g2_batch
            # dispatch below is folded away (ISSUE 5). Malformed lanes
            # stay per-lane False (None points contribute neutrally).
            sigs, sig_ok = self.engine.decompress_g2_batch(
                [sig for _, _, sig in items],
                subgroup_check=self.verify_inputs,
            )
            for i in range(n):
                if not sig_ok[i] or sigs[i] is None:
                    ok[i] = False
                    sigs[i] = None
        for i, (pk, data, sig) in enumerate(items):
            try:
                pks[i] = _cached_pubkey_point(pk)
                msgs[i] = _cached_msg_point(data)
                if not device_decode:
                    sigs[i] = sig_to_point(sig, subgroup_check=False)
                if sigs[i] is None:
                    raise TblsError("infinite signature")
            except TblsError:
                ok[i] = False
                pks[i] = msgs[i] = sigs[i] = None
        accepted = (
            self._rlc_guarded(items, pks, msgs, sigs)
            if n >= self.RLC_MIN_BATCH
            else False
        )
        if accepted:
            # the whole batch verified in one shared-final-exp program;
            # decode failures (ok[i] False) pass None lanes which
            # contribute neutrally and stay False below
            verified = [True] * n
        else:
            verified = self.engine.verify_batch(pks, msgs, sigs)
        in_subgroup = [True] * n
        if self.verify_inputs and not device_decode:
            # ship only lanes that decoded: known-False lanes (None)
            # would pad the batch for a check whose answer is unused
            live = [i for i in range(n) if sigs[i] is not None]
            if live:
                checked = self.engine.subgroup_check_g2_batch(
                    [sigs[i] for i in live]
                )
                for i, s in zip(live, checked):
                    in_subgroup[i] = s
        return [o and v and s for o, v, s in zip(ok, verified, in_subgroup)]

    def _rlc_guarded(self, items, pks, msgs, sigs) -> bool:
        """_rlc_accepts with device-failure containment: a COMPILE or
        runtime error on the accelerator is not a crypto verdict — step
        down the same degradation ladder as bench.py (fused-fp2 off with
        the jit caches cleared so the flag actually re-traces, then RLC
        off for this impl) and keep serving verifies on the per-lane
        engine rather than breaking the duty pipeline."""
        while True:
            try:
                return self._rlc_accepts(items, pks, msgs, sigs)
            except TblsError:
                raise
            except Exception as e:  # noqa: BLE001 — device/compile failure
                from charon_tpu.app import log
                from charon_tpu.ops import fptower

                from charon_tpu.ops import msm as MSM

                rung = self._degrade_rungs.pop(0) if self._degrade_rungs else None
                if rung == "msm-off" and not MSM.msm_active():
                    # another impl already burned this rung process-wide
                    rung = (
                        self._degrade_rungs.pop(0)
                        if self._degrade_rungs
                        else None
                    )
                if rung == "fp2-fusion-off" and not fptower._FP2_FUSION:
                    # another impl already burned this rung process-wide;
                    # retrying the identical path would fail identically
                    rung = None
                log.warn(
                    "RLC batch path failed on device; degrading",
                    topic="tbls",
                    err=f"{type(e).__name__}: {str(e)[:160]}",
                    rung=rung or "rlc-disabled",
                )
                if rung in ("msm-off", "fp2-fusion-off"):
                    from charon_tpu.ops import blsops

                    if rung == "msm-off":
                        MSM.set_msm(False)
                    else:
                        fptower.set_fp2_fusion(False)
                    # the flags are read at TRACE time: without dropping
                    # the cached jit wrappers the retry re-runs the
                    # identical compiled executable
                    blsops.clear_kernel_caches()
                    continue
                self.RLC_MIN_BATCH = 1 << 62  # disables RLC for this impl
                return False

    # At most this many distinct messages take the grouped kernel (one
    # Miller pair per message); beyond it, the ungrouped RLC kernel.
    RLC_MAX_GROUPS = 8

    def _rlc_accepts(self, items, pks, msgs, sigs) -> bool:
        """Whole-batch RLC check, grouped by message when few distinct
        messages exist (a DV cluster's common case: every validator in a
        committee signs the same attestation data, so a slot's partial
        sigs collapse to a handful of Miller pairs)."""
        distinct: dict[bytes, list[int]] = {}
        for i, (_, data, _) in enumerate(items):
            distinct.setdefault(data, []).append(i)
        if len(distinct) <= self.RLC_MAX_GROUPS:
            groups = []
            for data, lane_ids in distinct.items():
                lanes = [
                    (pks[i], sigs[i])
                    for i in lane_ids
                    if pks[i] is not None
                ]
                if lanes:
                    groups.append((_cached_msg_point(data), lanes))
            if not groups:
                return True  # nothing decodable; per-lane flags carry it
            return self.engine.verify_batch_grouped_rlc(groups)
        return self.engine.verify_batch_rlc(pks, msgs, sigs)

    def verify_aggregate(self, pubkeys: Sequence[bytes], data: bytes, sig: bytes) -> None:
        if not pubkeys:
            raise TblsError("no public keys")
        pts = [_cached_pubkey_point(pk) for pk in pubkeys]
        [agg_pk] = self.engine.aggregate_pks_batch([pts])
        if agg_pk is None:
            raise TblsError("aggregate public key is infinite")
        [sig_pt] = self._sig_points([sig], "signature")
        [ok] = self.engine.verify_batch(
            [agg_pk], [_cached_msg_point(data)], [sig_pt]
        )
        if not ok:
            raise TblsError("aggregate signature verification failed")

    # -- aggregation ------------------------------------------------------

    def threshold_aggregate(self, partials: Mapping[int, bytes]) -> bytes:
        return self.threshold_aggregate_batch([partials])[0]

    def threshold_aggregate_batch(self, batch) -> list[bytes]:
        if not batch:
            return []
        point_batch = []
        for partials in batch:
            if not partials:
                raise TblsError("no partial signatures")
            if any(i <= 0 for i in partials):
                raise TblsError("share indices are 1-based")
            flat = list(partials.items())
            pts = self._sig_points([s for _, s in flat], "partial signature")
            point_batch.append({i: pt for (i, _), pt in zip(flat, pts)})
        t = len(point_batch[0])
        if any(len(p) != t for p in point_batch):
            raise TblsError("inconsistent thresholds in batch")
        out = self.engine.threshold_aggregate_batch(point_batch)
        return [g1g2.g2_to_bytes(pt) for pt in out]

    def aggregate(self, sigs: Sequence[bytes]) -> bytes:
        return self.aggregate_batch([sigs])[0]

    def aggregate_batch(self, groups) -> list[bytes]:
        if not groups:
            return []
        point_groups = []
        for sigs in groups:
            if not sigs:
                raise TblsError("no signatures")
            point_groups.append(self._sig_points(sigs, "signature"))
        out = self.engine.aggregate_sigs_batch(point_groups)
        return [g1g2.g2_to_bytes(pt) for pt in out]
