"""Slot-tick coalescer: ONE sharded device program per flush for the
whole node's concurrent crypto work — with a pipelined host plane.

The reference executes crypto per duty per signature on the CPU as calls
arrive (ref: core/sigagg/sigagg.go:84-122 per-pubkey ThresholdAggregate +
verify; core/parsigex/parsigex.go:94-98 and
core/validatorapi/validatorapi.go:1213 per-signature herumi verifies).
A TPU inverts the economics: launching a program costs milliseconds while
extra lanes in a launched batch cost microseconds — so the win is
batching ACROSS concurrent duties, not just within one (SURVEY §7 step 4;
VERDICT r3 next-step 3).

SlotCoalescer is that batching point. Components submit work from the
event loop and await results; submissions arriving within one coalescing
window are merged:

  * verify lanes (pk, root, sig) from ParSigEx inbound sets, the
    ValidatorAPI's pubshare checks, and SigAgg — concatenated into one
    sharded RLC verify (`SlotCryptoPlane.verify_host`);
  * threshold recombination jobs [V, t] from SigAgg — concatenated along
    the validator axis into one sharded recombine+verify step
    (`SlotCryptoPlane.recombine_host`).

Pipeline (ISSUE 3): a flush passes through three host/device stages so
host work overlaps device work and the event loop never runs bigint
math:

      submit ──► decode pool ──► window ──► pack (decode pool)
                 (sqrt/h2c off                  │
                  the loop)                     ▼
                                        device lane (1 thread)

  * DECODE — point decompression and hash-to-curve are pure-Python
    bigint work (milliseconds per lane); submissions ship their items to
    a sized ThreadPoolExecutor in chunks, so a slot-tick burst of N
    partial sigs costs the loop microseconds instead of N×ms.
  * PACK — once a window closes, array packing and RLC randomness also
    run on the decode pool, so window k may pack while the device still
    executes window k-1 (double buffering). On the device decode rung
    the parsed signature lanes pack straight from their raw wire bytes
    into device-ready limb arrays in one vectorized numpy pass
    (ops/limb.bytes_to_limbs_batch via ops/decompress.pack_parsed_* —
    ISSUE 7), retiring the O(lanes*limbs) per-int conversion that used
    to dominate this stage.
  * DEVICE — a single serialized worker thread launches the compiled
    program, preserving the device-contention and counter-integrity
    guarantees of the original single-lane design.

What closes a window (`FlushStats.window_closed_by`, the
`cryptoplane.window` span's `closed_by`, `SlotCoalescer.windows_closed`):

  * "complete" — the wave is whole. A submission may say which waves it
    belongs to and what each waits for (`wave=((key, expected), ...)`).
    `expected` is a count of jobs (a duty's recombine job is one of 1),
    or it NAMES SENDERS (core/parsigex.WaveSet: a duty's
    partial-signature set says whose it is, `sender`; whose sets its
    wave waits for, `awaited`, always with the sender itself; and the
    cluster's operators, `n`). The submitters of one node take
    `awaited` from one roster (core/parsigex.WaveRoster): the operators
    whose set of the newest earlier slot of that duty type reached the
    verifier, all n before any has. The window keeps a ledger per
    (family, key), family being verify or recombine: jobs seen, the
    largest count hinted, the senders present and the UNION of the
    senders awaited. A wave is whole when
    it holds the jobs counted and a job of every awaited sender; the
    set of a sender that was not awaited (an operator back from an
    outage) rides along in the same flush if it is already there. As
    soon as every wave seen in the window is whole, every job in it
    carried a hint and no submission is still decoding, it closes at
    once: nothing more is waited for, so waiting buys nothing. Never
    on fewer than it awaits: the stragglers would flush alone on
    another bucket. `sets_expected` stays the static n whatever was
    awaited, so `sets_expected - sets_seen` still tells the outage;
    `sets_awaited` beside it (`FlushStats`, the span) is what the
    window waited for, and a window that closed "complete" on fewer
    than n counts in `SlotCoalescer.windows_closed_short`
    (`tpu_plane_windows_closed_short_total`).
  * "timer" — the window ran its length: an AWAITED sender's set is
    missing or late (the first slot of an operator's outage: it sent
    last slot, so it is waited for), or a job carried no hint (the
    remote client, a quarantine coalescer, tools): exactly the
    behaviour before hints. Such a close DOES feed the window
    controller below, and a short wave of two or more sets counts
    there as load, so the window after the first slot of an outage is
    x1.5 longer (toward `window_max`); from the second slot on the
    silent operators are not awaited, the verify windows close
    "complete" as the recombine windows do (one job expected, one
    seen) and feed the controller nothing, so nothing decays it
    either. A set that trails its wave's close flushes alone in the
    next window, on its timer, as a set later than the timer always
    did. How short the wave was is on the flush
    (`FlushStats.sets_expected` / `.sets_seen`, the
    `cryptoplane.window` span, `tpu_plane_wave_sets_short_total`):
    that tells a degraded cluster from unhinted traffic.
  * "deadline" / "pulled_earlier" — a submission carrying a duty
    deadline (core/deadline.SlotClock.duty_deadline) armed the window
    already capped, or pulled an armed one earlier, so near-deadline
    work never waits out a grown window. The graded cap
    (`DEADLINE_WINDOW_FRAC` of what the duty has left) is for jobs that
    do not say whom they wait for. A wave whose hints NAME the senders
    still awaited has its window for them and is capped by the deadline
    itself alone: it leaves the moment they are in, and cut short it
    would leave in two, the trailing set alone on a bucket of its own.

One kind a flush (`FlushStats.duty_types`, `duty_types` on the
`cryptoplane.window` / `.flush` / `.device` spans): the wave keys say
what duty each job belongs to — every key the node's submitters send
holds a core/types.Duty, and its `type` is the job's kind, whatever
types there are ("" for a job that named none). Two kinds of duty
triggered at the same instant (attestations and sync-committee messages
are both due at 1/3 slot) share the armed window and nothing else: each
kind has its own timer, from ITS first job, and leaves as a flush of
its own the moment ITS waves are whole ("complete") or its timer runs
out — on its own bucket, never on the bucket of the sum, never waiting
for the other kind's sets. One kind in the window is the same code with
one timer. A kind's verify and recombine jobs that do meet in the
window still leave as one flush, as they always did.

The device's order is a rule, not a race. Flushes that are ready for
the device lane together are taken most urgent first: the earliest duty
deadline, then the fewest lanes (`_urgency`), so the smaller wave's
program does not sit out the larger one's. And a packed flush yields
its turn while a more urgent flush of ANOTHER kind is on its way to the
lane — that kind's window is armed, a submission of it is still on the
decode pool (it carries its deadline, its lanes and the sets its wave
awaits from the moment it is made), or it is closed and being packed
(`_yield_turn`, `FlushStats.turn_yielded_s` / `.turn_yielded_to`,
`yielded` / `yielded_to` on `cryptoplane.flush`): which wave's last set
came 20 ms sooner does not decide the order on the device. It yields
for the other kind's own timer at most, never to a window that holds no
set, never to its own kind; its own window closed `complete` when its
wave was whole, as ever. One kind of duty in the window never waits.
A close that
sent off several kinds says so: `FlushStats.window_parts`, `parts` on
`cryptoplane.window`, `SlotCoalescer.windows_split`.

The window's length is adaptive: it grows toward `window_max` under
sustained multi-job load (catch more of the burst per program) and
decays back to the base once traffic thins. A window closed "complete"
feeds the controller nothing either way: a wave that came whole is no
evidence that waiting longer catches more.

Decode failures (malformed compressed points) never reach the device:
those lanes fail on host and are replaced by lane-0 padding in the batch.

The plane object only needs `t`, `verify_host`, and `recombine_host` —
production passes `parallel.mesh.SlotCryptoPlane`; fast-tier tests pass
a counting fake backed by the pure-python oracle. Planes that also
expose the packed two-stage API (`pack_verify_inputs`/`verify_packed`,
`pack_inputs`/`recombine_packed`) get the pipelined pack stage; others
fall back to the single-stage host API on the device lane.
"""

from __future__ import annotations

import asyncio
import heapq
import threading
import time
from dataclasses import dataclass, field
from typing import NamedTuple, Sequence

from charon_tpu.crypto import g1g2
from charon_tpu.tbls import TblsError

try:
    # the parse half of ops/decompress is pure host code, but the ops
    # PACKAGE init configures jax (x64) on import — on a jax-less host
    # the device decode rung is simply unavailable and the coalescer
    # stays on the python rung (the PR 2 ladder's floor).
    from charon_tpu.ops import decompress as _dec
except ImportError:  # pragma: no cover — jax not installed
    _dec = None


class _ParsedPointNA:
    """Sentinel parsed-lane type for jax-less hosts: nothing is ever an
    instance, so every isinstance() site degrades to the point path."""


_PARSED_T = _dec.ParsedPoint if _dec is not None else _ParsedPointNA


@dataclass
class _VerifyJob:
    lanes: list  # [(pk_pt, msg_pt, sig_pt) | None] — None = host decode fail
    fut: asyncio.Future
    decode_delays: tuple = ()  # decode-pool queue delay per chunk
    decode_spans: tuple = ()  # wall-clock (start, end) per decode chunk
    decode_hashed: tuple = ()  # message-cache misses per decode chunk
    parent: tuple | None = None  # submitter's (trace_id, span_id)
    tenant: str | None = None  # submitting tenant (core/cryptosvc)
    kind: str = ""  # the duty types its wave keys name (_kind_of)
    deadline: float | None = None  # wall clock, as submitted


@dataclass
class _RecombineJob:
    # all rows [V][t] / [V]; lanes with decode failures are pre-failed
    pubshares: list
    msgs: list
    partials: list
    group_pks: list
    indices: list
    prefail: list  # [V] bool — True: fail without consulting the device
    fut: asyncio.Future
    decode_delays: tuple = ()
    decode_spans: tuple = ()
    decode_hashed: tuple = ()
    parent: tuple | None = None
    tenant: str | None = None
    kind: str = ""
    deadline: float | None = None


@dataclass(frozen=True)
class FlushStats:
    """Per-flush pipeline observability, delivered to `stats_hook` from
    the device worker thread (thread-safe sinks only).

    The stage spans (wall-clock `time.time()` windows) plus the
    submitters' trace contexts in `parents` are everything
    app/tracer.plane_span_bridge needs to bridge the flush into real
    duty-rooted tracer spans; bench_hostplane.py computes its
    host/device overlap from the same fields."""

    jobs: int
    lanes: int
    flush_seconds: float  # device-lane wall clock (pack excluded)
    window: float  # adaptive window in force when the flush armed
    inflight: int  # device-lane depth at submit (1 when single-buffered
    # idle traffic; >= 2 means this flush double-buffered behind an
    # in-flight program)
    pad_lanes: int | None  # bucket-padding lanes shipped (packed path)
    padded_lanes: int | None  # total lanes after bucket padding
    decode_queue_seconds: tuple[float, ...]  # decode-pool queue delays
    fallback: bool = False  # served by the python-spec rung
    # decode-source breakdown of this flush (ISSUE 5): point lookups
    # served by the tpu_impl LRU caches (pubkeys/messages/pubshares) vs
    # signature lanes decompressed on device (parsed lanes shipped to a
    # decode-fused program) vs on host (python bigint decode)
    decode_mode: str = "python"  # decode rung that served the flush
    decode_cache_lanes: int = 0
    decode_device_lanes: int = 0
    decode_python_lanes: int = 0
    # wall-clock stage windows of THIS flush's pipeline pass
    decode_spans: tuple[tuple[float, float], ...] = ()  # per decode chunk
    # signing roots each of those chunks hashed to the curve (misses of
    # the tpu_impl message cache on the chunk's thread), and the engine
    # that hashes them in this process: "native" | "python"
    # (tpu_impl.MsgHashEngine), None while no root has missed yet
    decode_hashed: tuple[int, ...] = ()
    msg_hash_engine: str | None = None
    pack_span: tuple[float, float] | None = None
    device_span: tuple[float, float] | None = None
    # the coalescing window itself: first job into the idle coalescer ->
    # the window closed (what that job WAITED, where `window` above is
    # what was configured), and what closed it: "timer" (ran its
    # length), "deadline" (armed already capped by a duty deadline),
    # "pulled_earlier" (a later submission's deadline pulled it in),
    # "complete" (every hinted wave in it was whole: module docstring)
    window_span: tuple[float, float] | None = None
    window_closed_by: str = ""
    # the two queues apart (`jobs` is their sum: one window can hold
    # both), and the window's wave ledger summed over the verify waves
    # it held: partial-signature sets its submitters expected (the
    # cluster's n, whoever is silent), sets that came, and sets the
    # window AWAITED before it would close "complete" (fewer than
    # expected where the roster says operators sent nothing last slot).
    # None where the window held no verify wave or a job in it carried
    # no hint (the ledger is then not the whole story)
    verify_jobs: int = 0
    recombine_jobs: int = 0
    sets_expected: int | None = None
    sets_seen: int | None = None
    sets_awaited: int | None = None
    # closed "complete" with sets_awaited < sets_expected
    window_closed_short: bool = False
    # the duty types the flush's jobs named in their wave keys (a flush
    # holds ONE kind of duty: module docstring "One kind a flush"; more
    # than one only where a single job spans them; () where no job
    # named any), and how many flushes the close that made this one
    # dispatched: 1 unless several kinds closed in the same instant
    duty_types: tuple[str, ...] = ()
    window_parts: int = 1
    # seconds the packed flush yielded its device turn to a more urgent
    # kind's flush on its way to the lane, and which kind(s) that was
    # (`_yield_turn`; 0.0 and "" on every flush that had nobody to yield
    # to: one kind in the window, always)
    turn_yielded_s: float = 0.0
    turn_yielded_to: str = ""
    # the verify tiers: `attributed` where the RLC product over the
    # flush's lanes failed and the plane re-dispatched them through its
    # per-lane program (one pairing check a lane: `attribute_span` is
    # that dispatch's wall-clock window, `attribute_lanes` what it was
    # given). A lane that does not DECODE is answered by the RLC
    # program's own mask and attributes nothing. `set_resolved` where
    # the RLC tier refused at least one set and NO per-lane dispatch
    # followed: the parsed program takes its product per set (job), and
    # a failing set alone in its segment is answered False whole — what
    # its submitter does with it anyway — at no further dispatch.
    # `lanes_invalid` are the verify lanes of this flush answered False
    # or None, by whichever tier or by the host's parse (on a
    # set-resolved flush every lane of the refused set, honest or not:
    # None, not judged apart); `sets_invalid` the
    # jobs (partial-signature sets) holding at least one: each is
    # dropped whole by its submitter
    attributed: bool = False
    set_resolved: bool = False
    lanes_invalid: int = 0
    sets_invalid: int = 0
    attribute_span: tuple[float, float] | None = None
    attribute_lanes: int = 0
    # pairing lanes the flush's fast programs checked: its verify lanes
    # plus its live recombine rows — the recombine program checks ONE
    # lane a row, the group signature under the group key (the t
    # partials of a row were judged by the verify program when they
    # entered the node); `recombine_attributed` where that check failed
    # and the plane re-dispatched the rows through its per-lane
    # recombine program, which names the bad row (never on a cluster
    # whose verify tier does its work: a miss is a finding)
    pairing_lanes: int = 0
    recombine_attributed: bool = False
    # Miller pairs the flush's fast programs ran, from the buckets it
    # dispatched: bucket + VERIFY_SETS for a parsed verify dispatch (a
    # pair a lane, the key side, and ONE a set, the set's summed
    # signature: `_miller_pairs`), two a lane of the bucket for a
    # point-path verify or a recombine dispatch. Work, where
    # `pairing_lanes` is verdicts: padding lanes ride the scan too.
    # Counted where `padded_lanes` is, a packed flush: 0 on the
    # single-stage rung and the host-oracle fallback
    miller_pairs: int = 0
    # (trace_id, span_id) captured from each submission's active span
    parents: tuple[tuple[str, str], ...] = ()
    # live lanes per submitting tenant (ISSUE 8): (tenant_id, lanes)
    # pairs for the jobs that carried a tenant tag — the per-flush
    # attribution the tenant-labeled metric families and the span
    # bridge's tenant attrs are built from
    tenant_lanes: tuple[tuple[str, int], ...] = ()


# the plane's per-lane verify programs (parallel/mesh `on_program`
# families): one dispatched inside a flush says the RLC tier failed
_ATTRIBUTION_FAMILIES = frozenset({"mesh/verify", "mesh/verify_dec"})
# and its per-lane recombine programs: the group check of some row failed
_RECOMBINE_ATTRIBUTION_FAMILIES = frozenset({"mesh/step", "mesh/step_dec"})


class _Window(NamedTuple):
    """The window a flush closed, as it travels with the flush from the
    event loop to the device lane (and through the retry rungs)."""

    seconds: float = 0.0  # adaptive window in force when the flush armed
    span: tuple[float, float] | None = None  # wall clock: opened, closed
    closed_by: str = ""
    sets_expected: int | None = None  # FlushStats, same names
    sets_seen: int | None = None
    sets_awaited: int | None = None
    parts: int = 1  # FlushStats.window_parts
    yielded: float = 0.0  # FlushStats.turn_yielded_s
    yielded_to: str = ""  # FlushStats.turn_yielded_to

    @property
    def closed_short(self) -> bool:
        """Whole on fewer sets than the cluster has operators."""
        return (
            self.closed_by == "complete"
            and self.sets_awaited is not None
            and self.sets_awaited < self.sets_expected
        )


@dataclass
class _Wave:
    """One (family, key) of the armed window's ledger (module docstring
    "What closes a window")."""

    kind: str = ""  # the duty types its key names: whose timer it is on
    seen: int = 0  # jobs in the window
    expected: int = 0  # jobs its submitters expect at most: a count, or n
    counted: int = 0  # the largest plain count hinted
    present: set = field(default_factory=set)  # senders whose job is in
    awaited: set = field(default_factory=set)  # union over the hints

    def enter(self, expected) -> None:
        """One more job, hinted `expected`: a count, or naming senders
        (`sender`, `awaited`, `n`: core/parsigex.WaveSet). Hints that
        disagree keep the larger count and the union of the awaited: a
        window must never close on less than any submitter awaits."""
        self.seen += 1
        if isinstance(expected, int):
            self.counted = max(self.counted, expected)
            self.expected = max(self.expected, expected)
        else:
            self.present.add(expected.sender)
            self.awaited |= expected.awaited
            self.expected = max(self.expected, expected.n)

    @property
    def awaits(self) -> int:
        """Jobs the window waits for before this wave is whole."""
        return max(self.counted, len(self.awaited))

    @property
    def whole(self) -> bool:
        return self.seen >= self.counted and self.awaited <= self.present


@dataclass
class _Timer:
    """One kind of duty's share of the armed window: its own timer,
    from ITS first job (module docstring "One kind a flush")."""

    opened: float  # wall clock: the kind's first job into the window
    wall_offset: float  # wall->monotonic, snapshotted as the timer arms
    flush_at: float = 0.0  # monotonic: where its timer runs out
    # what running out is called: timer | deadline | pulled_earlier
    closed_by: str = "timer"
    queue_deadline: float | None = None  # monotonic, min over its jobs
    closing: bool = False  # its close has begun (_close_kind): not judged again


class _Decoding(NamedTuple):
    """A submission still on the decode pool, as far as the window can
    tell what it will be (one value of `_decode_tickets`)."""

    kind: str
    deadline: float | None  # wall clock, as submitted
    lanes: int
    awaits: int  # jobs its wave hints wait for (1 where it named none)


def _key_duty_type(key) -> str:
    """The duty type a wave key names: the `type` of the first thing in
    it that has one (core/types.Duty; a key is a duty, or a tuple that
    holds one, however its submitter or a tenant wrapped it)."""
    found = getattr(key, "type", None)
    if found is not None:
        return str(found)
    if isinstance(key, tuple):
        for part in key:
            found = _key_duty_type(part)
            if found:
                return found
    return ""


def _hint_awaits(wave) -> int:
    """Jobs the wave(s) a submission named wait for, by its own hints."""
    return max(
        (
            expected if isinstance(expected, int) else len(expected.awaited)
            for _key, expected in wave or ()
        ),
        default=1,
    )


def _kind_of(wave) -> str:
    """The kind of duty a submission belongs to, read off the wave keys
    it came with: their duty types (one, in every submission the node
    makes), "" where it named none or carried no hint."""
    return "+".join(sorted({_key_duty_type(key) for key, _ in wave or ()} - {""}))


class PlaneConfigError(ValueError):
    """Invalid crypto-plane configuration (typed-errors invariant: a
    config mistake at the plane boundary must be distinguishable from
    wire/crypto failures — it is a deploy bug, never degradable load)."""


def kernel_inventory() -> dict:
    """Machine-readable inventory of every registered device kernel
    family behind this plane (ISSUE 11): the blsops engine kernels plus
    the mesh program variants, registered on canonical bucket-ladder
    shapes. Consumers: the jaxpr static analyzer
    (charon_tpu/analysis/jaxpr_check.py traces each family and gates
    its primitive census against tests/testdata/kernel_manifest.json)
    and the per-platform startup auto-tuner (core/autotune.resolve
    walks this registry before micro-benching its candidate axes and
    records the family names in the persisted profile — ROADMAP item
    3). Raises PlaneConfigError on a
    jax-less host (asking for the device inventory without jax is a
    deploy/config mistake) — inventory is an analysis/tuning surface,
    not a duty-path one."""
    if _dec is None:
        raise PlaneConfigError(
            "kernel inventory requires jax (ops import failed)"
        )
    from charon_tpu.ops import blsops
    from charon_tpu.parallel import mesh as _mesh

    _mesh.register_analysis_families()
    return {
        name: {"sentinel": fam.sentinel}
        for name, fam in sorted(blsops.kernel_families().items())
    }


def _decode_pubkey(pk: bytes):
    from charon_tpu.tbls.tpu_impl import _cached_pubkey_point

    return _cached_pubkey_point(pk)


def _decode_sig(sig: bytes):
    from charon_tpu.tbls.python_impl import sig_to_point

    pt = sig_to_point(sig, subgroup_check=False)
    if pt is None:
        raise TblsError("infinite signature")
    return pt


def _msg_point(root: bytes):
    from charon_tpu.tbls.tpu_impl import _cached_msg_point

    return _cached_msg_point(root)


def _msg_hash_engine():
    from charon_tpu.tbls.tpu_impl import _decode_msg_point

    return _decode_msg_point


def _decode_verify_lane(item):
    """(pk, root, sig) bytes -> decoded point triple, or None on any
    malformed encoding (the lane fails on host, never ships)."""
    pk, root, sig = item
    try:
        return (_decode_pubkey(pk), _msg_point(root), _decode_sig(sig))
    except (TblsError, ValueError):
        return None


def _parse_verify_lane(item):
    """decode_mode=device twin of _decode_verify_lane: the pubkey and
    message still come from the host LRU caches, but the signature is
    only PARSED (flags + range checks, no field arithmetic) — the Fp2
    sqrt, sign selection, on-curve and subgroup checks run batched on
    device inside the flush program. The pubkey always hits (the key
    table is warmed at boot). The message cannot be warmed (a signing
    root does not exist before its slot): the first job of a wave
    misses once per distinct root (31-32 an attester wave), as does a
    set that arrives while those are still being hashed, and pays
    tpu_impl.MsgHashEngine for each — ~6 ms, most of it native code
    with the GIL released, ~14 ms of GIL-held bigints on a host
    without the library; every later set hits. That is the wave's one
    wide `cryptoplane.decode` span (`msg_hashed` counts its misses). Lanes
    the parse already rejects (malformed flags, x >= p, infinity) fail
    on host and never ship."""
    pk, root, sig = item
    try:
        pk_pt, msg_pt = _decode_pubkey(pk), _msg_point(root)
    except (TblsError, ValueError):
        return None
    parsed = _dec.parse_g2_lane(sig)
    if not parsed.ok or parsed.infinity:
        return None
    return (pk_pt, msg_pt, parsed)


def _lane_to_points(lane):
    """Parsed verify lane -> point triple on the python rung (device
    decode unavailable / degraded). Point lanes pass through; a parsed
    signature that fails host decompression turns the lane into None."""
    if lane is None or not isinstance(lane[2], _PARSED_T):
        return lane
    try:
        return (lane[0], lane[1], _decode_sig(lane[2].raw))
    except (TblsError, ValueError):
        return None


class SlotCoalescer:
    """Merges concurrent verify / recombine submissions into single
    sharded device programs (see module docstring).

    window: base seconds to wait after the first submission before
    flushing; the adaptive controller moves the live window within
    [window, window_max] under load and deadlines cap it down to
    window_min. The wait ends early when the window's waves are whole
    (`wave=` on verify / recombine; module docstring "What closes a
    window"): such a close leaves the controller as it was. Without
    hints, or an awaited set short, the timer closes it as before.
    decode_workers: decode/pack pool size; 0 disables the pipeline
    entirely (decode runs synchronously on the caller — the pre-pipeline
    path, kept for A/B benching). The pool is created lazily on first
    use, so an idle or disabled plane owns no threads.
    flushes / coalesced_flushes / lanes_flushed / windows_closed (by
    cause) / windows_closed_short / windows_split / turns_yielded /
    flushes_attributed /
    flushes_set_resolved / lanes_invalid / flushes_recombine_attributed:
    observability counters (exported as node metrics by app/run.py).
    """

    # submitters may pass `wave=` (TenantPlane says the same; the remote
    # client and test fakes do not, and their callers send no hint)
    wave_hints = True

    # decode-pool chunking: large enough to amortize executor submission,
    # small enough to spread one burst across the workers
    DECODE_CHUNK = 16
    # adaptive window controller: grow when a flush coalesced >=2 jobs or
    # carried a burst, decay back to the base window otherwise
    WINDOW_GROW = 1.5
    WINDOW_DECAY = 0.75
    GROW_LANES = 64
    # graded deadline shrink: spend at most this fraction of the time
    # remaining before the duty deadline on coalescing — with a 60 s
    # expiry the cap is ~0.56 s at 1/3 slot (far above the default
    # 20-80 ms windows), and a retrying near-expiry submission (seconds
    # left) flushes in milliseconds instead of waiting out a load-grown
    # window. Not applied to a wave that names the senders it still
    # waits for (`_arm`)
    DEADLINE_WINDOW_FRAC = 0.01

    def __init__(
        self,
        plane,
        window: float = 0.02,
        plane_factory=None,
        window_min: float = 0.002,
        window_max: float = 0.08,
        decode_workers: int = 4,
        stats_hook=None,
        decode_mode: str = "auto",
    ):
        import concurrent.futures

        # per-lane verify dispatches of the flush now on the device
        # lane: (wall-clock start, end, lanes), filled by _listen's hook
        # and emptied as the next flush's verify stage begins
        self._attributions: list[tuple[float, float, int]] = []
        # and whether its recombine stage fell to the per-lane program
        self._recombine_attributed = False
        self.plane = self._listen(plane)
        self.window = window
        self.window_min = min(window_min, window)
        self.window_max = max(window_max, window)
        self.decode_workers = decode_workers
        # signature-decode routing (ISSUE 5): "device" parses compressed
        # signatures on host (cheap flag/range checks) and runs the
        # field work (sqrt, sign, on-curve, psi subgroup) batched inside
        # the flush program via the plane's *_parsed API; "python" keeps
        # the host bigint decode; "auto" resolves to device only on a
        # TPU backend with a parsed-capable plane. python is ALSO the
        # degradation rung below device (PR 2 ladder): a device failure
        # in a parsed flush steps this coalescer down permanently.
        if decode_mode not in ("auto", "device", "python"):
            raise PlaneConfigError(f"bad decode_mode {decode_mode!r}")
        self.decode_mode = decode_mode
        self._decode_live: str | None = None  # resolved lazily
        # msm-off degradation rung (mirrors tbls/tpu_impl._rlc_guarded):
        # a device/compile failure in the newest kernel family is not a
        # crypto verdict. plane_factory() rebuilds the plane after the
        # flag flip so its jitted programs re-trace; without a factory
        # there is no degrade at all (the flag stays untouched — a retry
        # without a rebuild would re-run the identical failed executable).
        self._plane_factory = plane_factory
        self._degraded = False
        self._closed = False
        self._verify_q: list[_VerifyJob] = []
        self._recombine_q: list[_RecombineJob] = []
        self._flush_task: asyncio.Task | None = None
        self._flush_wake = asyncio.Event()
        # the armed window's timers, one a kind of duty in it ("" for
        # jobs that named none): a kind leaves when ITS waves are whole
        # or ITS timer runs out (module docstring "One kind a flush")
        self._timers: dict[str, _Timer] = {}
        # submissions mid-decode, each with its kind (a kind's close
        # waits for its own) and what it will be (`_collecting_urgency`)
        self._decode_tickets: dict[asyncio.Future, _Decoding] = {}
        # the armed window's wave ledger by (family, key), and how many
        # of its jobs carried no hint (their kind is "")
        self._waves: dict[tuple, _Wave] = {}
        self._unhinted_jobs = 0
        # flushes between their window's close and their results
        self._parts: set[asyncio.Task] = set()
        # flushes packed and waiting for the device lane, which takes
        # the most urgent first: (deadline, lanes, seq, fn, args, future)
        self._ready: list[tuple] = []
        self._ready_lock = threading.Lock()
        self._ready_seq = 0
        # flushes between their close and the lane's heap, each with its
        # urgency, and the flushes that yield their turn to one of those
        # or to a kind still collecting (`_yield_turn`)
        self._packing: dict[object, tuple[str, tuple]] = {}
        self._yielding: list[asyncio.Future] = []
        self._window_current = window
        # first-dispatch gate (app/run.py wires the autotune tune_done
        # event here): the boot-time tuner's trial.apply() flips the
        # global dispatch flags and drops the jitted-kernel caches, so
        # a flush racing the tuning window compiles under a transient
        # trial config and immediately loses its executable. Flushes
        # queue behind the gate (and keep coalescing) until it fires;
        # None (tests, CLI tools, no tuner) means no gating at all.
        self.dispatch_gate: asyncio.Event | None = None
        self.gated_flushes = 0  # flushes that waited on dispatch_gate
        # single-threaded device lane: a second window can elapse while a
        # device program is still running; its flush must QUEUE behind
        # the first, not race it (device contention + counter integrity)
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="crypto-plane"
        )
        # decode/pack pool — created lazily so a coalescer that never
        # sees traffic (or runs with decode_workers=0) owns no threads
        self._decode_pool: concurrent.futures.ThreadPoolExecutor | None = None
        self.flushes = 0
        self.windows_closed: dict[str, int] = {}  # by `closed_by` cause
        # of the "complete" ones: verify windows whole on fewer sets
        # than the cluster has operators (the roster awaited fewer)
        self.windows_closed_short = 0
        # closes that sent off more than one kind, a flush each
        self.windows_split = 0
        # flushes that yielded their device turn to a more urgent one
        self.turns_yielded = 0
        self.coalesced_flushes = 0  # flushes that merged >= 2 jobs
        self.lanes_flushed = 0
        self.flushes_attributed = 0  # fell to the per-lane verify tier
        # the RLC tier refused a set and answered for it: no such fall
        self.flushes_set_resolved = 0
        self.lanes_invalid = 0  # verify lanes answered False
        # the recombine program's group check failed: per-lane re-dispatch
        self.flushes_recombine_attributed = 0
        self.host_fallback_flushes = 0  # served by the python-spec rung
        self.pack_fallbacks = 0  # pack-stage failures (single-stage flush)
        self.pad_lanes_flushed = 0  # bucket-padding lanes shipped
        self.overlapped_flushes = 0  # submitted while the device was busy
        self._inflight = 0  # flushes inside the device lane (incl. queued)
        self.max_inflight = 0
        # per-flush pipeline stats (FlushStats), delivered on the device
        # worker thread: thread-safe sinks only. Stage timing travels IN
        # the stats (window/decode/pack/device wall-clock windows), so
        # the tracer bridge and bench_hostplane.py both read per-flush
        # spans from here instead of a coalescer-global trace list.
        self.stats_hook = stats_hook
        # bulk warm-up observability (ISSUE 6): called with the stats
        # dict of every warm_caches() pass (worker thread — thread-safe
        # sinks only); counters for the /metrics families
        self.warmup_hook = None
        self.warmups = 0
        self.warmup_lanes = 0

    def _listen(self, plane):
        """Stand in front of the plane's program hook (a plane that has
        one: parallel/mesh `on_program`), so that a flush can say which
        verify tier answered it and whether its recombine stage needed
        the per-lane program; whoever held the hook is still called,
        and whoever takes it later chains to this."""
        if not hasattr(plane, "on_program"):
            return plane
        inner = plane.on_program

        def hook(family: str, seconds: float, lanes: int) -> None:
            if family in _ATTRIBUTION_FAMILIES:
                end = time.time()  # lint: allow(monotonic-clock) — a span's wall clock
                self._attributions.append((end - seconds, end, lanes))
            elif family in _RECOMBINE_ATTRIBUTION_FAMILIES:
                self._recombine_attributed = True
            if inner is not None:
                inner(family, seconds, lanes)

        plane.on_program = hook
        return plane

    @property
    def t(self) -> int:
        return self.plane.t

    # -- decode-mode resolution (ISSUE 5) ----------------------------------

    def _plane_has_parsed_api(self) -> bool:
        return self._plane_has_packed_api() and all(
            hasattr(self.plane, name)
            for name in (
                "pack_verify_inputs_parsed",
                "verify_packed_parsed",
                "pack_inputs_parsed",
                "recombine_packed_parsed",
            )
        )

    def _decode_rung(self) -> str:
        """The decode rung in force: 'device' ships parsed signature
        lanes to decode-fused programs, 'python' decompresses on host.
        Resolved once, lazily: 'auto' means device only on a TPU backend
        (CPU sqrt chains are slower than the host bigints they replace)
        AND a parsed-capable plane; a forced 'device' still needs the
        plane API (test fakes without it stay on python). A device
        failure in a parsed flush steps the live rung down to python
        permanently (PR 2 ladder)."""
        if self._decode_live is None:
            mode = self.decode_mode
            if _dec is None or not self._plane_has_parsed_api():
                mode = "python"
            elif mode == "auto":
                # the parsed API implies a real jax plane, so this
                # import resolves to the already-loaded module
                from charon_tpu.ops import limb

                mode = "device" if limb._is_tpu_backend() else "python"
            self._decode_live = mode
        return self._decode_live

    @property
    def current_window(self) -> float:
        """The adaptive coalescing window currently in force."""
        return self._window_current

    def close(self) -> None:
        """Shut down the worker pools (idempotent). Late flushes fail
        their waiters fast instead of tripping the degradation rung."""
        self._closed = True
        self._executor.shutdown(wait=False)
        if self._decode_pool is not None:
            self._decode_pool.shutdown(wait=False)
            self._decode_pool = None

    # -- decode pool (host stage 1) ---------------------------------------

    def _pool(self):
        if self._decode_pool is None:
            import concurrent.futures

            self._decode_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.decode_workers,
                thread_name_prefix="crypto-decode",
            )
        return self._decode_pool

    async def _map_offloop(self, fn, items: list):
        """Apply `fn` per item with the bigint work OFF the event loop:
        items ship to the decode pool in DECODE_CHUNK chunks (batched
        submission — one executor hop per chunk, not per lane). Returns
        (results, per-chunk queue delays, per-chunk wall-clock spans,
        per-chunk message-cache misses) — the last three travel with the
        job so each flush's stats report ITS OWN decode queueing, timing
        and hashing, not whatever the concurrent next window happens to
        be decoding. With the pool disabled the map runs inline on the
        caller — the pre-pipeline synchronous path bench_hostplane.py
        baselines."""
        engine = _msg_hash_engine()

        def hashing(chunk):
            """fn over the chunk, and the roots it hashed on the way
            (the engine counts per thread: chunks run side by side)."""
            before = engine.on_thread()
            out = [fn(it) for it in chunk]
            return out, engine.on_thread() - before

        # closed: inline decode instead of resurrecting a pool nobody
        # will shut down (the flush fails these waiters fast anyway)
        if self.decode_workers <= 0 or self._closed:
            # stage spans are ATTRIBUTION: wall-clock windows bridged
            # into duty traces (tracer.plane_span_bridge), never math
            w0 = time.time()  # lint: allow(monotonic-clock)
            out, hashed = hashing(items)
            return out, (), ((w0, time.time()),), (hashed,)  # lint: allow(monotonic-clock)
        loop = asyncio.get_running_loop()
        pool = self._pool()
        submitted = time.monotonic()

        def run_chunk(chunk):
            t0 = time.monotonic()
            # wall span = trace attribution; the queue DELAY above it
            # stays on the monotonic base
            w0 = time.time()  # lint: allow(monotonic-clock)
            out, hashed = hashing(chunk)
            return out, t0 - submitted, (w0, time.time()), hashed  # lint: allow(monotonic-clock)

        chunks = [
            items[i : i + self.DECODE_CHUNK]
            for i in range(0, len(items), self.DECODE_CHUNK)
        ]
        parts = await asyncio.gather(
            *(loop.run_in_executor(pool, run_chunk, c) for c in chunks)
        )
        return (
            [lane for part, _, _, _ in parts for lane in part],
            tuple(delay for _, delay, _, _ in parts),
            tuple(span for _, _, span, _ in parts),
            tuple(hashed for _, _, _, hashed in parts),
        )

    # -- submission APIs (event-loop side) --------------------------------

    @staticmethod
    def _submit_ctx():
        """(trace_id, span_id) of the submitting context's active span —
        how a flush's stage spans find their way into the duty traces
        whose work they merged (app/tracer.plane_span_bridge)."""
        from charon_tpu.app.tracer import current_ctx  # lazy: core !-> app

        return current_ctx()

    async def verify(
        self,
        items: Sequence[tuple[bytes, bytes, bytes]],
        deadline: float | None = None,
        tenant: str | None = None,
        wave: Sequence[tuple[object, int]] | None = None,
    ) -> list[bool | None]:
        """Batch-verify (pubkey_bytes, signing_root, sig_bytes) lanes.
        Returns per-lane validity; malformed encodings are False. One
        call is one SET, accepted or dropped whole by its submitter:
        where the plane's RLC tier judges per set (parsed lanes), the
        lanes of a call holding a well-formed signature that does not
        verify are None — falsy: refused with their set, the honest
        ones beside the forged one too, and not judged apart
        (FlushStats.set_resolved); False is a lane known bad.
        deadline: optional absolute wall-clock (time.time) duty deadline
        — pulls the flush earlier when the window would overshoot it.
        tenant: optional tenant id (core/cryptosvc) for per-flush
        attribution in FlushStats/metrics/span attrs.
        wave: optional ((key, expected), ...) — this job belongs to the
        verify wave `key`, which waits for `expected`: a count of jobs,
        or named senders (a duty's partial-signature set says whose it
        is and whose sets its wave awaits: core/parsigex.WaveSet); a
        window whose waves are all whole closes without waiting out its
        timer (module docstring)."""
        if not items:
            return []
        loop = asyncio.get_running_loop()
        # decode ticket: an armed flush whose window closes while this
        # submission is still decoding WAITS for it — otherwise a burst
        # whose cold-cache decode outlasts the window would split into
        # one device program per submission (the anti-coalescing bug)
        ticket = loop.create_future()
        kind = _kind_of(wave)
        self._decode_tickets[ticket] = _Decoding(
            kind, deadline, len(items), _hint_awaits(wave)
        )
        try:
            decode_fn = (
                _parse_verify_lane
                if self._decode_rung() == "device"
                else _decode_verify_lane
            )
            lanes, delays, spans, hashed = await self._map_offloop(
                decode_fn, list(items)
            )
            job = _VerifyJob(
                lanes=lanes,
                fut=loop.create_future(),
                decode_delays=delays,
                decode_spans=spans,
                decode_hashed=hashed,
                parent=self._submit_ctx(),
                tenant=tenant,
                kind=kind,
                deadline=deadline,
            )
            self._verify_q.append(job)
            self._count_wave("verify", wave, kind)
            self._arm(deadline, kind)
        finally:
            # resolve AFTER the append above (same synchronous block):
            # the waiting flush wakes only on the next scheduler turn,
            # so the job is guaranteed to be in the collected queue
            self._decode_tickets.pop(ticket, None)
            if not ticket.done():
                ticket.set_result(None)
            self._close_if_whole()
            # a flush that yields its turn to this kind counted the
            # submission as on its way: it is in now, or never will be
            self._lane_moved()
        return await job.fut

    async def recombine(
        self,
        pubshares: Sequence[Sequence[bytes]],
        roots: Sequence[bytes],
        partials: Sequence[Sequence[bytes]],
        group_pks: Sequence[bytes],
        indices: Sequence[Sequence[int]],
        deadline: float | None = None,
        tenant: str | None = None,
        wave: Sequence[tuple[object, int]] | None = None,
    ) -> tuple[list[bytes | None], list[bool]]:
        """Threshold-recombine + verify a duty's [V, t] workload.
        Returns ([V] group signature bytes or None, [V] ok flags).
        wave: as verify(); recombine jobs are counted apart from verify
        jobs of the same key (a duty's recombine job is one of 1)."""
        if not roots:
            return [], []
        t = self.plane.t
        device_decode = self._decode_rung() == "device"

        def parse_partial(sig: bytes):
            parsed = _dec.parse_g2_lane(sig)
            if not parsed.ok or parsed.infinity:
                raise TblsError("malformed partial signature")
            return parsed

        def decode_row(row):
            ps_row, root, sig_row, gpk, idx_row = row
            try:
                if len(sig_row) != t or len(ps_row) != t or len(idx_row) != t:
                    raise TblsError(f"need exactly t={t} partials per lane")
                if any(i <= 0 for i in idx_row):
                    raise TblsError("share indices are 1-based")
                return (
                    [_decode_pubkey(p) for p in ps_row],
                    _msg_point(root),
                    # device rung: partials ship as PARSED lanes (no
                    # field arithmetic here) — the flush program
                    # decompresses them; host-parse rejects prefail
                    [
                        parse_partial(s) if device_decode else _decode_sig(s)
                        for s in sig_row
                    ],
                    _decode_pubkey(gpk),
                    list(idx_row),
                    False,
                )
            except (TblsError, ValueError):
                # prefail row — skipped during batch assembly (never
                # shipped to the device); the lane is failed on host
                return (None, None, None, None, None, True)

        loop = asyncio.get_running_loop()
        ticket = loop.create_future()  # see verify() for the contract
        kind = _kind_of(wave)
        self._decode_tickets[ticket] = _Decoding(
            kind, deadline, len(roots), _hint_awaits(wave)
        )
        try:
            rows, delays, spans, hashed = await self._map_offloop(
                decode_row,
                list(zip(pubshares, roots, partials, group_pks, indices)),
            )
            ps_rows, msg_pts, sig_rows, gpk_pts, idx_rows, prefail = (
                [list(col) for col in zip(*rows)]
            )
            job = _RecombineJob(
                pubshares=ps_rows,
                msgs=msg_pts,
                partials=sig_rows,
                group_pks=gpk_pts,
                indices=idx_rows,
                prefail=prefail,
                fut=loop.create_future(),
                decode_delays=delays,
                decode_spans=spans,
                decode_hashed=hashed,
                parent=self._submit_ctx(),
                tenant=tenant,
                kind=kind,
                deadline=deadline,
            )
            self._recombine_q.append(job)
            self._count_wave("recombine", wave, kind)
            self._arm(deadline, kind)
        finally:
            self._decode_tickets.pop(ticket, None)
            if not ticket.done():
                ticket.set_result(None)
            self._close_if_whole()
            # a flush that yields its turn to this kind counted the
            # submission as on its way: it is in now, or never will be
            self._lane_moved()
        sigs_pts, oks = await job.fut
        return (
            [
                g1g2.g2_to_bytes(pt) if pt is not None else None
                for pt in sigs_pts
            ],
            oks,
        )

    # -- flush machinery ---------------------------------------------------

    def _count_wave(self, family: str, wave, kind: str = "") -> None:
        """Enter the job just appended into the window's wave ledger."""
        if not wave:
            self._unhinted_jobs += 1
            return
        for key, expected in wave:
            self._waves.setdefault((family, key), _Wave(kind)).enter(expected)

    def _verify_sets(
        self, kind: str = ""
    ) -> tuple[int | None, int | None, int | None]:
        """The armed window's ledger summed over `kind`'s verify waves:
        (sets expected, sets seen, sets awaited) — Nones where it held
        none or a job of the kind came without a hint."""
        waves = [
            wave
            for (family, _key), wave in self._waves.items()
            if family == "verify" and wave.kind == kind
        ]
        if not waves or (not kind and self._unhinted_jobs):
            return None, None, None
        return (
            sum(w.expected for w in waves),
            sum(w.seen for w in waves),
            sum(w.awaits for w in waves),
        )

    def _kind_whole(self, kind: str) -> bool:
        """Nothing more is waited for by `kind`'s jobs in the armed
        window: every one said which wave it belongs to, every such
        wave holds what it awaits, and no submission of the kind is
        still decoding. Another kind's waves, short or whole, say
        nothing here."""
        waves = [w for w in self._waves.values() if w.kind == kind]
        return bool(
            waves
            and not (not kind and self._unhinted_jobs)
            and not any(d.kind == kind for d in self._decode_tickets.values())
            and all(w.whole for w in waves)
        )

    def _awaits_named(self, kind: str) -> bool:
        """Some wave of `kind` in the armed window names senders (its
        submitters' roster) whose set it still waits for."""
        return any(
            w.kind == kind and not w.awaited <= w.present
            for w in self._waves.values()
        )

    def _window_whole(self) -> bool:
        """Some kind in the armed window is whole: its flush can leave."""
        return any(
            self._kind_whole(kind)
            for kind, timer in self._timers.items()
            if not timer.closing
        )

    def _close_if_whole(self) -> None:
        """Wake the flush task when a kind's waves are whole; it judges
        again when it runs, so a job that joins in between keeps its
        kind waiting for ITS wave. Otherwise a kind leaves as windows
        always closed: timer, deadline, pulled earlier."""
        if self._window_whole():
            self._flush_wake.set()

    @property
    def _flush_at(self) -> float | None:
        """Monotonic: where the first of the armed timers runs out (None:
        every kind in the window is already closing)."""
        return min(
            (t.flush_at for t in self._timers.values() if not t.closing),
            default=None,
        )

    def _arm(self, deadline: float | None = None, kind: str = "") -> None:
        now = time.monotonic()
        timer = self._timers.get(kind)
        if timer is None:
            # duty deadlines are wall-clock (core/deadline.SlotClock)
            # but the flush timer runs on the monotonic base — snapshot
            # the wall->monotonic offset ONCE per timer. Converting per
            # call meant a host clock step mid-window (chaos clock-skew)
            # translated later submissions' deadlines inconsistently,
            # wrongly collapsing or stretching the armed window.
            # the timer's `opened` is its span's start
            # (FlushStats.window_span): trace attribution on the wall
            # clock, never math
            wall = time.time()  # lint: allow(monotonic-clock) — THE one-shot wall->mono anchor (PR 8 fix)
            timer = _Timer(opened=wall, wall_offset=now - wall)
        if deadline is not None:
            dl_mono = max(now, deadline + timer.wall_offset)
            if timer.queue_deadline is None or dl_mono < timer.queue_deadline:
                timer.queue_deadline = dl_mono
        target = now + self._window_current
        if timer.queue_deadline is not None:
            remaining = timer.queue_deadline - now
            if self._awaits_named(kind):
                # its waves' roster NAMES the senders still waited for:
                # the window is theirs to arrive in, and the moment they
                # have the kind leaves "complete". Cutting it short on a
                # share of what the duty has left would send the wave off
                # in two, the straggler on a bucket of its own. Never
                # past the deadline itself
                cap = remaining
            else:
                # graded shrink toward the deadline, never below
                # window_min (give concurrent submissions a beat to
                # coalesce regardless)
                cap = max(
                    self.window_min, remaining * self.DEADLINE_WINDOW_FRAC
                )
            target = min(target, now + cap)
        if kind not in self._timers:
            # a kind joins the armed window (or arms it)
            timer.flush_at = target
            timer.closed_by = (
                "deadline" if target < now + self._window_current else "timer"
            )
            self._timers[kind] = timer
        elif target < timer.flush_at:
            # a tighter deadline arrived while the kind's timer sleeps:
            # pull it earlier (never later)
            timer.flush_at = target
            timer.closed_by = "pulled_earlier"
        else:
            return
        if self._flush_task is None or self._flush_task.done():
            # fresh Event per armed task: asyncio primitives bind to the
            # running loop on first use, and one coalescer may serve
            # several asyncio.run() lifetimes (tests, CLI tools)
            self._flush_wake = asyncio.Event()
            self._flush_task = asyncio.create_task(self._flush_after_window())
        else:
            # the task sleeps toward the earliest timer: now maybe this
            self._flush_wake.set()

    async def _flush_after_window(self) -> None:
        """The armed window: sleeps toward the earliest of its kinds'
        timers, and each time it wakes sends off every kind that is
        whole ("complete") or whose timer has run out, each as a flush
        of its own, closed in a task of its own — a kind whose close
        waits (the dispatch gate, its own submissions mid-decode) holds
        no other kind back. Ends when no kind is left in it; the next
        submission arms a fresh one."""
        while self._timers:
            self._flush_wake.clear()
            now = time.monotonic()
            closing = {}
            for kind, timer in self._timers.items():
                if timer.closing:
                    continue
                if self._kind_whole(kind):
                    closing[kind] = "complete"
                elif timer.flush_at <= now:
                    closing[kind] = timer.closed_by
            if len(closing) > 1:
                self.windows_split += 1
            for kind, closed_by in closing.items():
                self._timers[kind].closing = True
                self._own(self._close_kind(kind, closed_by, len(closing)))
            flush_at = self._flush_at
            try:
                # a close that has taken its jobs wakes this loop too
                await asyncio.wait_for(
                    self._flush_wake.wait(),
                    timeout=None if flush_at is None else max(0.0, flush_at - now),
                )
            except asyncio.TimeoutError:
                pass
        self._flush_task = None

    def _own(self, coro) -> None:
        """A task between a kind's close and its jobs' results."""
        task = asyncio.create_task(coro)
        self._parts.add(task)
        task.add_done_callback(self._parts.discard)

    async def _close_kind(self, kind: str, closed_by: str, parts: int) -> None:
        """Take `kind`'s jobs out of the armed window and dispatch them
        as ONE flush. `parts`: how many kinds closed in the same instant."""
        # read before the first await below: a submission arriving from
        # here on may still pull the timer, but the kind has closed
        closed = time.time()  # lint: allow(monotonic-clock)
        gate = self.dispatch_gate
        if gate is not None and not gate.is_set():
            # startup tuner still settling the kernel dispatch flags:
            # queue this flush behind it. Waiting BEFORE the snapshot
            # also lets submissions arriving during the tuning window
            # coalesce into this flush instead of arming more of them.
            self.gated_flushes += 1
            await gate.wait()
        # submissions of the kind still mid-decode when it closed join
        # its flush (ONE snapshot — later arrivals take the next window,
        # so sustained load cannot defer a flush unboundedly)
        pending = [
            ticket
            for ticket, decoding in self._decode_tickets.items()
            if decoding.kind == kind
        ]
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)
        # new submissions of the kind from here on arm a fresh timer —
        # their decode/pack stages overlap this flush's device stage
        timer = self._timers.pop(kind)
        vq = [job for job in self._verify_q if job.kind == kind]
        rq = [job for job in self._recombine_q if job.kind == kind]
        self._verify_q = [j for j in self._verify_q if j.kind != kind]
        self._recombine_q = [j for j in self._recombine_q if j.kind != kind]
        sets = self._verify_sets(kind)
        self._waves = {k: w for k, w in self._waves.items() if w.kind != kind}
        if not kind:
            self._unhinted_jobs = 0
        self._flush_wake.set()  # the window's task: one kind fewer
        if not vq and not rq:
            self._lane_moved()
            return
        if self._closed:
            # shutdown raced a late submission: fail the waiters fast —
            # a closed-executor RuntimeError must not masquerade as a
            # device failure and burn the msm-off rung
            for job in [*vq, *rq]:
                if not job.fut.done():
                    job.fut.set_exception(TblsError("crypto plane closed"))
            self._lane_moved()
            return
        window_used = _Window(
            self._window_current,
            (timer.opened, closed),
            closed_by,
            *sets,
            parts=parts,
        )
        self.windows_closed[closed_by] = self.windows_closed.get(closed_by, 0) + 1
        if window_used.closed_short:
            self.windows_closed_short += 1
        if closed_by != "complete":
            # a wave that came whole is no evidence that waiting longer
            # catches more (nor that traffic thinned): controller untouched
            self._adapt_window(vq, rq)
        await self._flush_part(kind, vq, rq, window_used)

    @staticmethod
    def _urgency(vq, rq) -> tuple[float, int]:
        """What the device lane orders waiting flushes by: the earliest
        duty deadline among the jobs (none: last), then the fewest
        lanes — of two kinds due at the same instant the smaller wave
        does not sit out the larger one's program."""
        deadlines = [j.deadline for j in (*vq, *rq) if j.deadline is not None]
        return (
            min(deadlines, default=float("inf")),
            sum(len(j.lanes) for j in vq) + sum(len(j.msgs) for j in rq),
        )

    def _collecting_urgency(self, kind: str) -> tuple[float, int]:
        """`_urgency` of the flush `kind`'s submissions will leave as, as
        far as the coalescer can tell: the earliest deadline among the
        jobs in the armed window and the submissions still decoding, and
        their lanes scaled from the sets in to the sets their waves wait
        for."""
        vq = [j for j in self._verify_q if j.kind == kind]
        rq = [j for j in self._recombine_q if j.kind == kind]
        coming = [d for d in self._decode_tickets.values() if d.kind == kind]
        deadline, lanes = self._urgency(vq, rq)
        deadline = min(
            [deadline, *(d.deadline for d in coming if d.deadline is not None)]
        )
        lanes += sum(d.lanes for d in coming)
        waves = [w for w in self._waves.values() if w.kind == kind]
        seen = sum(w.seen for w in waves) + len(coming)
        if seen:
            awaits = max(
                sum(max(w.awaits, w.seen) for w in waves),
                max((d.awaits for d in coming), default=0),
                seen,
            )
            lanes = -(-lanes * awaits // seen)
        return deadline, lanes

    def _lane_moved(self) -> None:
        """A kind left the armed window, a flush reached the lane's heap
        or a submission came off the decode pool: the flushes that yield
        their turn look again."""
        yielding, self._yielding = self._yielding, []
        for waiter in yielding:
            if not waiter.done():
                waiter.set_result(None)

    def _more_urgent(self, kind: str, mine: tuple[float, int]) -> set[str]:
        """The OTHER kinds of duty whose flush is on its way to the lane
        and more urgent than `mine`: still collecting (a timer is armed
        from a kind's first job, and a submission on the decode pool is
        one that will arm it: either way a set of the kind is in), or
        closed and being packed."""
        collecting = set(self._timers) | {
            d.kind for d in self._decode_tickets.values()
        }
        return {
            other
            for other in collecting
            if other != kind and self._collecting_urgency(other) < mine
        } | {
            other
            for other, urgency in self._packing.values()
            if other != kind and urgency < mine
        }

    async def _yield_turn(
        self, kind: str, mine: tuple[float, int]
    ) -> tuple[float, str] | None:
        """Before a flush asks for its device turn: while a MORE urgent
        flush of ANOTHER kind is on its way to the lane — a kind still
        collecting in the armed window, or one closed and being packed —
        wait for it to get there, so that the order on the device is
        `_urgency`'s and not the order in which two waves due at the same
        instant happened to become whole (the smaller wave's duties would
        sit out the larger one's program because its last set came 20 ms
        later). At most the other kind's timer: a kind leaves the window
        whole or when its timer runs out, and is packed in milliseconds.
        Equal urgency yields to nobody, a flush never yields to its own
        kind (a straggler's window is not waited out), and with no other
        kind armed or packing the flush goes at once: one kind in the
        window never waits. Returns the seconds it yielded and the kinds
        it yielded to, None if it did not."""
        began, to = None, set()
        while ahead := self._more_urgent(kind, mine):
            to |= ahead
            if began is None:
                began = time.monotonic()
            waiter = asyncio.get_running_loop().create_future()
            self._yielding.append(waiter)
            await waiter
        if began is None:
            return None
        return time.monotonic() - began, "+".join(sorted(to))

    def _device_turn(self) -> None:
        """Device lane: run the most urgent flush that is ready, and
        hand its waiter the outcome, whatever it is."""
        with self._ready_lock:
            *_, fn, args, done = heapq.heappop(self._ready)
        try:
            done.set_result(fn(*args))
        except BaseException as e:  # noqa: BLE001 — the waiter's to judge
            done.set_exception(e)

    async def _on_device_lane(self, urgency, fn, *args):
        """`fn(*args)` on the serialized device lane, in its turn: every
        ready flush takes one turn, and a turn runs the most urgent."""
        import concurrent.futures

        done: concurrent.futures.Future = concurrent.futures.Future()
        with self._ready_lock:
            self._ready_seq += 1
            entry = (*urgency, self._ready_seq, fn, args, done)
            heapq.heappush(self._ready, entry)
        try:
            self._executor.submit(self._device_turn)
        except BaseException:
            # no turn was given (the lane shut down under us): the entry
            # leaves with its flush, for no later turn to find
            with self._ready_lock:
                self._ready.remove(entry)
                heapq.heapify(self._ready)
            raise
        return await asyncio.wrap_future(done)

    async def _flush_part(
        self, kind: str, vq, rq, window_used: _Window
    ) -> None:
        """One flush, from its window's close to its jobs' results."""
        urgency = self._urgency(vq, rq)
        token = object()
        self._packing[token] = (kind, urgency)
        try:
            packed = await self._pack_part(vq, rq)
            # still among `_packing` while it yields: a less urgent flush
            # waits for this one too, whichever of them wakes first
            yielded = await self._yield_turn(kind, urgency)
            if yielded is not None:
                self.turns_yielded += 1
                seconds, to = yielded
                window_used = window_used._replace(
                    yielded=seconds, yielded_to=to
                )
        finally:
            del self._packing[token]
            # the turn is asked for before this task next yields
            # (`_on_device_lane` pushes first): whoever yields to this
            # flush finds it on the heap
            self._lane_moved()
        await self._dispatch_part(vq, rq, packed, urgency, window_used)

    async def _pack_part(self, vq, rq):
        """Host stage 2: pack the batch on the decode pool so the device
        lane (possibly still executing the previous window) is never
        blocked on numpy conversion of Python ints."""
        loop = asyncio.get_running_loop()
        packed = None
        if self.decode_workers > 0 and self._plane_has_packed_api():
            try:
                packed = await loop.run_in_executor(
                    self._pool(), self._pack_flush, vq, rq
                )
            except Exception as e:  # noqa: BLE001 — pack bug: the
                # single-stage path repacks on the device lane, which
                # still serves the flush but silently un-pipelines it —
                # count + warn so a persistent pack failure is visible
                packed = None
                self.pack_fallbacks += 1
                if self.pack_fallbacks == 1 or self.pack_fallbacks % 100 == 0:
                    from charon_tpu.app import log

                    log.warn(
                        "crypto plane pack stage failed; flushing "
                        "single-stage on the device lane",
                        topic="cryptoplane",
                        count=self.pack_fallbacks,
                        err=f"{type(e).__name__}: {str(e)[:160]}",
                    )
        return packed

    async def _dispatch_part(
        self, vq, rq, packed, urgency, window_used: _Window
    ) -> None:
        """A packed flush's device turn, and its jobs' results."""
        loop = asyncio.get_running_loop()
        inflight = self._inflight + 1
        self._inflight = inflight
        self.max_inflight = max(self.max_inflight, inflight)
        if inflight >= 2:
            self.overlapped_flushes += 1
        try:
            try:
                vres, rres = await self._on_device_lane(
                    urgency,
                    self._run_device,
                    vq,
                    rq,
                    packed,
                    window_used,
                    inflight,
                )
            except Exception as e:  # noqa: BLE001 — degrade or fail waiters
                # first rung below the device decode: step decode down
                # to python for good and retry the SAME batch — the
                # decode-fused programs are the newest kernel family, so
                # a failure there must not cost the older point-input
                # path (or burn the process-wide msm-off rung)
                retried = await self._decode_stepdown_and_retry(
                    vq, rq, e, window_used, inflight
                )
                if retried is None:
                    retried = await self._degrade_and_retry(
                        vq, rq, e, window_used, inflight
                    )
                if retried is None:
                    # last rung: the pure-python spec oracle. Orders of
                    # magnitude slower than the device, but a wedged
                    # accelerator must cost latency, not the duty — the
                    # signing plane stays live on the degraded backend
                    # (ISSUE: degrade TPU -> native -> python-spec).
                    try:
                        retried = await loop.run_in_executor(
                            self._executor, self._run_host_oracle, vq, rq
                        )
                        self.host_fallback_flushes += 1
                        from charon_tpu.app import log

                        log.warn(
                            "crypto plane flush served by python-spec "
                            "host fallback",
                            topic="cryptoplane",
                            rung="host-oracle",
                            err=f"{type(e).__name__}: {str(e)[:160]}",
                        )
                    except Exception:  # noqa: BLE001 — rungs exhausted
                        for job in [*vq, *rq]:
                            if not job.fut.done():
                                job.fut.set_exception(
                                    TblsError(
                                        f"crypto plane flush failed: {e}"
                                    )
                                )
                        return
                vres, rres = retried
        finally:
            self._inflight -= 1
        for job, res in zip(vq, vres):
            if not job.fut.done():
                job.fut.set_result(res)
        for job, res in zip(rq, rres):
            if not job.fut.done():
                job.fut.set_result(res)

    def _adapt_window(self, vq, rq) -> None:
        """Sustained load (multi-job windows or lane bursts) grows the
        window toward window_max — each program catches more of the
        burst; light traffic decays it back to the base so single duties
        never wait out a grown window."""
        jobs = len(vq) + len(rq)
        lanes = sum(len(j.lanes) for j in vq) + sum(len(j.msgs) for j in rq)
        if jobs >= 2 or lanes >= self.GROW_LANES:
            self._window_current = min(
                self.window_max, self._window_current * self.WINDOW_GROW
            )
        else:
            self._window_current = max(
                self.window, self._window_current * self.WINDOW_DECAY
            )

    def _plane_has_packed_api(self) -> bool:
        return all(
            hasattr(self.plane, name)
            for name in (
                "pack_verify_inputs",
                "make_lane_rand",
                "verify_packed",
                "pack_inputs",
                "make_rand",
                "recombine_packed",
            )
        )

    @staticmethod
    def _flat_verify_lanes(vq: list[_VerifyJob]) -> list:
        return [lane for job in vq for lane in job.lanes if lane is not None]

    @staticmethod
    def _flat_verify_sets(vq: list[_VerifyJob]) -> list[int]:
        """Which job each of _flat_verify_lanes' lanes came from: a job
        is one partial-signature set, accepted or dropped whole, and the
        parsed verify program judges its lanes as one."""
        return [
            k
            for k, job in enumerate(vq)
            for lane in job.lanes
            if lane is not None
        ]

    def _normalize_jobs(self, vq, rq) -> bool:
        """One flush, one lane representation (worker thread). Returns
        True when the flush ships PARSED signature lanes to the
        decode-fused device programs. That needs the device rung still
        live AND every lane parsed — a rung step-down between
        submissions can leave a window holding both kinds, and the
        retry of a failed parsed flush arrives here after the step-down;
        in either case the parsed lanes convert to points on host (the
        python rung), flipping a job's prefail slot when a partial
        fails host decompression. Idempotent, cheap when nothing is
        parsed."""
        kinds = set()
        for job in vq:
            for lane in job.lanes:
                if lane is not None:
                    kinds.add(isinstance(lane[2], _PARSED_T))
        for job in rq:
            for i, pf in enumerate(job.prefail):
                if not pf:
                    kinds.add(
                        isinstance(job.partials[i][0], _PARSED_T)
                    )
        if True not in kinds:
            return False
        if kinds == {True} and self._decode_rung() == "device":
            return True
        for job in vq:
            job.lanes = [_lane_to_points(lane) for lane in job.lanes]
        for job in rq:
            for i in range(len(job.msgs)):
                if job.prefail[i] or not isinstance(
                    job.partials[i][0], _PARSED_T
                ):
                    continue
                try:
                    job.partials[i] = [
                        _decode_sig(p.raw) for p in job.partials[i]
                    ]
                except (TblsError, ValueError):
                    job.prefail[i] = True
        return False

    @staticmethod
    def _live_recombine_rows(rq: list[_RecombineJob]):
        ps, msg, sig, gpk, idx = [], [], [], [], []
        for job in rq:
            for i in range(len(job.msgs)):
                if not job.prefail[i]:
                    ps.append(job.pubshares[i])
                    msg.append(job.msgs[i])
                    sig.append(job.partials[i])
                    gpk.append(job.group_pks[i])
                    idx.append(job.indices[i])
        return ps, msg, sig, gpk, idx

    def _pack_flush(self, vq, rq):
        """Decode-pool thread: array packing + RLC randomness for the
        whole flush. Returns (vpack, rpack, pack_span) for _run_device's
        packed fast path — this is the half of the old verify_host/
        recombine_host work that does NOT need the device lane."""
        # pack span = wall-clock trace attribution (FlushStats bridge)
        w0 = time.time()  # lint: allow(monotonic-clock)
        plane = self.plane
        parsed = self._normalize_jobs(vq, rq)
        vpack = None
        flat = self._flat_verify_lanes(vq)
        if flat:
            pks, msgs, sigs = zip(*flat)
            if parsed:
                arrays = plane.pack_verify_inputs_parsed(
                    pks, msgs, sigs, self._flat_verify_sets(vq)
                )
            else:
                arrays = plane.pack_verify_inputs(pks, msgs, sigs)
            vpack = (arrays, plane.make_lane_rand(len(flat)), len(flat), parsed)
        rpack = None
        ps, msg, sig, gpk, idx = self._live_recombine_rows(rq)
        if msg:
            pack = plane.pack_inputs_parsed if parsed else plane.pack_inputs
            rpack = (
                pack(ps, msg, sig, gpk, idx),
                plane.make_rand(len(msg)),
                len(msg),
                parsed,
            )
        return vpack, rpack, (w0, time.time())  # lint: allow(monotonic-clock)

    # -- device side (worker thread) --------------------------------------

    def _run_device(
        self,
        vq: list[_VerifyJob],
        rq: list[_RecombineJob],
        packed=None,
        window_used: _Window = _Window(),
        inflight: int = 1,
    ):
        # counters update only AFTER both stages succeed: a failed flush
        # that the degrade rung retries must not double-count its lanes
        t0 = time.monotonic()
        # device span = wall-clock trace attribution; durations use t0
        w0 = time.time()  # lint: allow(monotonic-clock)
        vpack, rpack, pack_span = (
            packed if packed is not None else (None, None, None)
        )
        if packed is None:
            # single-stage flush (pool disabled / pack failed): lane
            # normalization runs here on the device lane instead
            parsed = self._normalize_jobs(vq, rq)
        lanes = miller_pairs = 0
        pad_lanes = padded_lanes = 0 if packed is not None else None
        vres: list[list[bool]] = []
        self._attributions.clear()
        self._recombine_attributed = False
        if vq:
            if vpack is not None:
                # flat lane count came with the pack — don't re-flatten
                # on the serialized device lane
                arrays, rand, n, vparsed = vpack
                verify = (
                    self.plane.verify_packed_parsed
                    if vparsed
                    else self.plane.verify_packed
                )
                oks = iter(verify(arrays, rand, n))
                shipped = self._packed_lane_count(arrays)
                pad_lanes += shipped - n
                padded_lanes += shipped
                miller_pairs += self._miller_pairs(shipped, sets=vparsed)
            else:
                flat = self._flat_verify_lanes(vq)
                n = len(flat)
                if flat and parsed:
                    pks, msgs, sigs = zip(*flat)
                    arrays = self.plane.pack_verify_inputs_parsed(
                        pks, msgs, sigs, self._flat_verify_sets(vq)
                    )
                    oks = iter(
                        self.plane.verify_packed_parsed(
                            arrays, self.plane.make_lane_rand(n), n
                        )
                    )
                elif flat:
                    pks, msgs, sigs = zip(*flat)
                    oks = iter(self.plane.verify_host(pks, msgs, sigs))
                else:
                    oks = iter(())
            for job in vq:
                vres.append(
                    [
                        next(oks) if lane is not None else False
                        for lane in job.lanes
                    ]
                )
            lanes += n
        rres: list[tuple[list, list[bool]]] = []
        if rq:
            if rpack is not None:
                arrays, rand, v, rparsed = rpack
                recombine = (
                    self.plane.recombine_packed_parsed
                    if rparsed
                    else self.plane.recombine_packed
                )
                out_sigs, out_oks = recombine(arrays, rand, v)
                shipped = self._packed_lane_count(arrays)
                pad_lanes += shipped - v
                padded_lanes += shipped
                miller_pairs += self._miller_pairs(shipped)
            else:
                ps, msg, sig, gpk, idx = self._live_recombine_rows(rq)
                if msg and parsed:
                    args = self.plane.pack_inputs_parsed(
                        ps, msg, sig, gpk, idx
                    )
                    out_sigs, out_oks = self.plane.recombine_packed_parsed(
                        args, self.plane.make_rand(len(msg)), len(msg)
                    )
                elif msg:
                    out_sigs, out_oks = self.plane.recombine_host(
                        ps, msg, sig, gpk, idx
                    )
                else:
                    out_sigs, out_oks = [], []
            it_sig, it_ok = iter(out_sigs), iter(out_oks)
            live_rows = 0
            for job in rq:
                sigs_pts: list = []
                oks: list[bool] = []
                for pf in job.prefail:
                    if pf:
                        sigs_pts.append(None)
                        oks.append(False)
                    else:
                        sigs_pts.append(next(it_sig))
                        oks.append(next(it_ok))
                        live_rows += 1
                rres.append((sigs_pts, oks))
            lanes += live_rows
        mode, cache_n, device_n, python_n = self._decode_breakdown(vq, rq)
        self._account_flush(
            vq,
            rq,
            lanes,
            FlushStats(
                jobs=len(vq) + len(rq),
                lanes=lanes,
                flush_seconds=time.monotonic() - t0,
                window=window_used.seconds,
                inflight=inflight,
                pad_lanes=pad_lanes,
                padded_lanes=padded_lanes,
                decode_queue_seconds=self._job_decode_delays(vq, rq),
                decode_mode=mode,
                decode_cache_lanes=cache_n,
                decode_device_lanes=device_n,
                decode_python_lanes=python_n,
                decode_spans=self._job_decode_spans(vq, rq),
                decode_hashed=self._job_decode_hashed(vq, rq),
                msg_hash_engine=_msg_hash_engine().name,
                pack_span=pack_span,
                device_span=(w0, time.time()),  # lint: allow(monotonic-clock)
                window_span=window_used.span,
                window_closed_by=window_used.closed_by,
                verify_jobs=len(vq),
                recombine_jobs=len(rq),
                sets_expected=window_used.sets_expected,
                sets_seen=window_used.sets_seen,
                sets_awaited=window_used.sets_awaited,
                window_closed_short=window_used.closed_short,
                duty_types=self._job_duty_types(vq, rq),
                window_parts=window_used.parts,
                turn_yielded_s=window_used.yielded,
                turn_yielded_to=window_used.yielded_to,
                **self._verify_verdicts(vres, self._attributions),
                # verify lanes + live recombine rows, a lane each
                pairing_lanes=lanes,
                recombine_attributed=self._recombine_attributed,
                miller_pairs=miller_pairs,
                parents=self._job_parents(vq, rq),
                tenant_lanes=self._job_tenant_lanes(vq, rq),
            ),
        )
        return vres, rres

    @staticmethod
    def _verify_verdicts(vres: list[list[bool | None]], ran=()) -> dict:
        """FlushStats' verify-tier fields, from the verdicts a flush is
        about to fan back to its jobs (None: a lane refused with its
        set by the RLC tier, which only a flush with no per-lane
        dispatch answers) and the per-lane dispatches (start, end,
        lanes) the plane reported while it made them."""
        bad = [sum(1 for ok in job if not ok) for job in vres]
        return {
            "attributed": bool(ran),
            "set_resolved": any(ok is None for job in vres for ok in job),
            "lanes_invalid": sum(bad),
            "sets_invalid": sum(1 for b in bad if b),
            "attribute_span": (ran[0][0], ran[-1][1]) if ran else None,
            "attribute_lanes": sum(lanes for _s, _e, lanes in ran),
        }

    def _miller_pairs(self, bucket: int, sets: bool = False) -> int:
        """Miller pairs one RLC dispatch of `bucket` lanes (rows) runs:
        two a lane — (r * pk, H(m)) and (r * -G1, sig) — except in the
        parsed verify program (`sets`), which sums a set's r * sig in G2
        and pairs (-G1, sum) once a set: a pair a lane and one for each
        of the plane's VERIFY_SETS segments (ops/pairing.
        batched_verify_rlc_sets)."""
        if sets:
            return bucket + getattr(self.plane, "VERIFY_SETS", 0)
        return 2 * bucket

    @staticmethod
    def _packed_lane_count(arrays) -> int:
        """Leading-axis size of a packed batch = lanes after bucket
        padding (the live mask is the last element of every pack)."""
        live = arrays[-1]
        return int(live.shape[0])

    @staticmethod
    def _job_decode_delays(vq, rq) -> tuple[float, ...]:
        """Decode-pool queue delays of exactly THIS flush's jobs."""
        return tuple(
            delay for job in [*vq, *rq] for delay in job.decode_delays
        )

    @staticmethod
    def _job_decode_spans(vq, rq) -> tuple:
        """Wall-clock decode windows of exactly THIS flush's jobs."""
        return tuple(
            span for job in [*vq, *rq] for span in job.decode_spans
        )

    @staticmethod
    def _job_decode_hashed(vq, rq) -> tuple[int, ...]:
        """Message-cache misses of exactly THIS flush's jobs, one count
        per decode chunk (parallel to _job_decode_spans)."""
        return tuple(
            n for job in [*vq, *rq] for n in job.decode_hashed
        )

    def _decode_breakdown(self, vq, rq) -> tuple[str, int, int, int]:
        """(mode, cache_lanes, device_lanes, python_lanes) of a flush:
        cache_lanes counts point lookups served by the tpu_impl LRU
        caches (pubkey + message per verify lane; pubshares + message +
        group pubkey per recombine row), device/python_lanes count
        signature lanes by decode rung. The mode is what actually
        shipped; a flush with NO live signature lanes (every lane
        prefailed on host parse) reports the rung in force instead, so
        the tpu_plane_decode_mode gauge never fakes a ladder step-down
        off a fully-malformed window."""
        cache = device = python = 0
        for job in vq:
            for lane in job.lanes:
                if lane is None:
                    continue
                cache += 2
                if isinstance(lane[2], _PARSED_T):
                    device += 1
                else:
                    python += 1
        for job in rq:
            for i, pf in enumerate(job.prefail):
                if pf:
                    continue
                cache += len(job.pubshares[i]) + 2
                if isinstance(job.partials[i][0], _PARSED_T):
                    device += len(job.partials[i])
                else:
                    python += len(job.partials[i])
        if device:
            mode = "device"
        elif python:
            mode = "python"
        else:
            mode = self._decode_live or "python"
        return mode, cache, device, python

    @staticmethod
    def _job_parents(vq, rq) -> tuple:
        """Submitting-span contexts of this flush's jobs (deduped by
        the bridge, ordered by submission)."""
        return tuple(
            job.parent for job in [*vq, *rq] if job.parent is not None
        )

    @staticmethod
    def _job_duty_types(vq, rq) -> tuple[str, ...]:
        """The duty types this flush's jobs named (FlushStats.duty_types)."""
        return tuple(sorted(
            {t for job in (*vq, *rq) for t in job.kind.split("+") if t}
        ))

    @staticmethod
    def _job_tenant_lanes(vq, rq) -> tuple:
        """Live lanes per submitting tenant (ISSUE 8). Untagged jobs
        (single-tenant deployments bypassing the service) contribute
        nothing — the aggregate counters already cover them."""
        per: dict[str, int] = {}
        for job in vq:
            if job.tenant is not None:
                per[job.tenant] = per.get(job.tenant, 0) + sum(
                    1 for lane in job.lanes if lane is not None
                )
        for job in rq:
            if job.tenant is not None:
                per[job.tenant] = per.get(job.tenant, 0) + sum(
                    1 for pf in job.prefail if not pf
                )
        return tuple(sorted(per.items()))

    def _account_flush(self, vq, rq, lanes: int, stats: FlushStats) -> None:
        self.lanes_flushed += lanes
        self.flushes += 1
        self.flushes_attributed += 1 if stats.attributed else 0
        self.flushes_set_resolved += 1 if stats.set_resolved else 0
        self.lanes_invalid += stats.lanes_invalid
        self.flushes_recombine_attributed += (
            1 if stats.recombine_attributed else 0
        )
        if stats.pad_lanes:
            self.pad_lanes_flushed += stats.pad_lanes
        if len(vq) + len(rq) >= 2:
            self.coalesced_flushes += 1
        if self.stats_hook is not None:
            self.stats_hook(stats)

    async def _decode_stepdown_and_retry(
        self, vq, rq, err, window_used: _Window = _Window(), inflight: int = 1
    ):
        """Decode-ladder rung (ISSUE 5): a failed flush that shipped
        PARSED lanes steps this coalescer's decode rung down to python
        permanently, converts the batch's parsed signatures to points
        on host, and retries the same batch through the point-input
        programs. Returns (vres, rres) or None when inapplicable (the
        flush wasn't parsed) or the retry itself failed — the caller
        continues down the existing msm-off / host-oracle ladder.

        Applicability is judged by the BATCH (did parsed lanes ship?),
        not by the current rung: with double-buffered windows a second
        in-flight parsed flush can fail AFTER the first one already
        stepped the rung down, and it must still retry here instead of
        burning the process-wide msm-off rung on a decode-family
        failure."""
        if self._closed:
            return None
        parsed = any(
            lane is not None and isinstance(lane[2], _PARSED_T)
            for job in vq
            for lane in job.lanes
        ) or any(
            not pf and isinstance(job.partials[i][0], _PARSED_T)
            for job in rq
            for i, pf in enumerate(job.prefail)
        )
        if not parsed:
            return None
        from charon_tpu.app import log

        log.warn(
            "crypto plane parsed flush failed on device; decode "
            + (
                "stepping down to python"
                if self._decode_live == "device"
                else "rung already stepped down; retrying on python"
            ),
            topic="cryptoplane",
            rung="decode-python",
            err=f"{type(err).__name__}: {str(err)[:160]}",
        )
        self._decode_live = "python"

        def convert_and_run():
            # worker thread: _normalize_jobs sees the stepped-down rung
            # and host-decodes every parsed lane before the device pass
            return self._run_device(vq, rq, None, window_used, inflight)

        try:
            loop = asyncio.get_running_loop()
            return await loop.run_in_executor(self._executor, convert_and_run)
        except Exception:  # noqa: BLE001 — continue down the ladder
            return None

    async def _degrade_and_retry(
        self, vq, rq, err, window_used: _Window = _Window(), inflight: int = 1
    ):
        """One-shot msm-off rung: flip the MSM family off, rebuild the
        plane so its programs re-trace, and retry the SAME batch on the
        per-lane path. Returns (vres, rres) or None if the rung is spent
        / inapplicable / the retry also failed."""
        from charon_tpu.ops import blsops
        from charon_tpu.ops import msm as MSM

        if isinstance(
            err,
            (TypeError, ValueError, KeyError, IndexError,
             AttributeError, AssertionError, TblsError),
        ):
            # host-side bug classes (shape/tracing/logic errors): the
            # per-lane path would hit the same bug, and permanently
            # disabling the process-wide MSM fast path + paying a
            # minutes-long plane rebuild on the duty path buys nothing
            # (ADVICE r4: gate the rung on device/compile error types)
            return None
        if (
            self._closed
            or self._degraded
            or not MSM.msm_active()
            or self._plane_factory is None
        ):
            # no factory -> no retry: the plane's jitted programs are
            # per-instance, so without a rebuild the retry would re-run
            # the identical failed executable
            return None
        self._degraded = True
        from charon_tpu.app import log

        log.warn(
            "crypto plane flush failed on device; degrading",
            topic="cryptoplane",
            rung="msm-off",
            err=f"{type(err).__name__}: {str(err)[:160]}",
        )
        MSM.set_msm(False)
        blsops.clear_kernel_caches()

        def rebuild_and_run():
            # worker thread, NOT the event loop: the factory touches
            # jax.devices()/compilation, which blocks for minutes (a
            # pairing program is minutes of compile)
            self.plane = self._listen(self._plane_factory())
            return self._run_device(vq, rq, None, window_used, inflight)

        try:
            loop = asyncio.get_running_loop()
            return await loop.run_in_executor(self._executor, rebuild_and_run)
        except Exception:  # noqa: BLE001 — rung spent; caller fails waiters
            return None

    # -- pre-warm (startup) ------------------------------------------------

    async def prewarm(
        self,
        verify_lanes: Sequence[int] | None = None,
        recombine_lanes: Sequence[int] | None = None,
    ) -> list:
        """Trace + compile the canonical duty-path shapes on the device
        lane so the first live slot never eats a cold pairing compile.
        None defers to the plane's bucket-ladder defaults (smallest
        bucket + canonical burst shapes). Runs through the same
        serialized executor as flushes (a live flush queues behind the
        compile instead of racing it). Returns the plane's
        [(kind, lanes, seconds)] compile report; [] when the plane has
        no prewarm support (test fakes)."""
        fn = getattr(self.plane, "prewarm", None)
        if fn is None:
            return []
        kwargs = {}
        if self._decode_rung() == "device":
            # also compile the decode-fused program family — live
            # flushes on the device rung land on those shapes
            import inspect

            if "decompress" in inspect.signature(fn).parameters:
                kwargs["decompress"] = True
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._executor,
            lambda: fn(
                verify_lanes=(
                    None if verify_lanes is None else tuple(verify_lanes)
                ),
                recombine_lanes=(
                    None
                    if recombine_lanes is None
                    else tuple(recombine_lanes)
                ),
                **kwargs,
            ),
        )

    # -- bulk cache warm-up (ISSUE 6) --------------------------------------

    def _plane_has_warm_api(self) -> bool:
        return all(
            hasattr(self.plane, name)
            for name in ("hash_to_g2_host", "decompress_g1_host")
        )

    def _warm_sync(
        self, pubkeys: list, messages: list, chunk: int | None
    ) -> dict:
        """Worker-thread body of warm_caches: bulk-decode through the
        plane's sharded warm programs (device rung) or per-point host
        decode (python rung / jax-less host), feeding the tpu_impl
        point caches via PointCache.put."""
        try:
            from charon_tpu.tbls import tpu_impl
        except Exception:  # pragma: no cover — jax-less host without
            # the tbls device backend: there are no point caches to
            # warm; report the skip instead of failing startup
            return {
                "pubkey": {"skipped": len(pubkeys)},
                "message": {"skipped": len(messages)},
                "seconds": 0.0,
            }
        device = (
            self._decode_rung() == "device" and self._plane_has_warm_api()
        )
        plane = self.plane

        class _PlaneWarmEngine:
            """Adapter: the plane's sharded warm programs behind the
            BlsEngine bulk-decode surface warm_point_caches drives."""

            @staticmethod
            def decompress_g1_batch(batch, subgroup_check=True):
                return plane.decompress_g1_host(batch)

            @staticmethod
            def hash_to_g2_batch(batch):
                return plane.hash_to_g2_host(batch)

        return tpu_impl.warm_point_caches(
            pubkeys=pubkeys,
            messages=messages,
            engine=_PlaneWarmEngine() if device else None,
            device=device,
            # None = inherit tpu_impl.WARMUP_CHUNK — one default for
            # every warm path, documented in docs/operations.md
            chunk=chunk if chunk is not None else tpu_impl.WARMUP_CHUNK,
        )

    async def warm_caches(
        self,
        pubkeys: Sequence[bytes] = (),
        messages: Sequence[bytes] = (),
        chunk: int | None = None,
    ) -> dict:
        """Bulk-populate the point caches for a key/message set — the
        startup and validator-set-rotation hook (ISSUE 6). On the
        device decode rung the field work (G1 decompression with the
        GLV subgroup check, hash-to-curve SSWU + isogeny + psi cofactor
        clearing) runs as chunked sharded device programs; the python
        rung decodes per point on host (still off the event loop).

        Runs on its OWN short-lived worker thread, NEVER the serialized
        device lane: a live flush racing a warm-up must not queue
        behind thousands of warm lanes (device dispatches interleave in
        XLA's stream; host stages run in parallel). Idempotent — keys
        already cached are skipped — so a rotation re-warm costs only
        the new entries. Returns the per-cache stats dict and feeds it
        to `warmup_hook`."""
        import concurrent.futures

        loop = asyncio.get_running_loop()
        ex = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="crypto-warmup"
        )
        try:
            stats = await loop.run_in_executor(
                ex,
                self._warm_sync,
                list(pubkeys),
                list(messages),
                chunk,
            )
        finally:
            ex.shutdown(wait=False)
        self.warmups += 1
        self.warmup_lanes += sum(
            n
            for cache in ("pubkey", "message")
            for src, n in stats.get(cache, {}).items()
            if src in ("device", "python")
        )
        if self.warmup_hook is not None:
            self.warmup_hook(stats)
        return stats

    # -- python-spec host fallback (worker thread) -------------------------

    @staticmethod
    def _oracle_verify_lane(pk_pt, msg_pt, sig_pt) -> bool:
        from charon_tpu.crypto.bls import G1_GEN, g1_neg
        from charon_tpu.crypto.pairing_fast import (
            is_gt_one,
            multi_pairing_fast,
        )

        return is_gt_one(
            multi_pairing_fast([(sig_pt, g1_neg(G1_GEN)), (msg_pt, pk_pt)])
        )

    def _run_host_oracle(self, vq: list[_VerifyJob], rq: list[_RecombineJob]):
        """Serve the SAME batch shape as _run_device on the pure-python
        spec backend (crypto/bls + crypto/shamir): per-lane pairing
        verify and Lagrange recombination on decoded points. No device,
        no jitted programs — the rung below every accelerator failure."""
        from charon_tpu.crypto import shamir

        t0 = time.monotonic()
        w0 = time.time()  # lint: allow(monotonic-clock) — device span is trace attribution
        # a parsed flush can land here when every device rung failed:
        # force the python lane representation first (worker thread —
        # the bigint decompression belongs here, not the event loop)
        self._decode_live = "python"
        self._normalize_jobs(vq, rq)
        lanes = 0
        vres: list[list[bool]] = []
        for job in vq:
            out = []
            for lane in job.lanes:
                if lane is None:
                    out.append(False)
                    continue
                out.append(self._oracle_verify_lane(*lane))
                lanes += 1
            vres.append(out)
        rres: list[tuple[list, list[bool]]] = []
        for job in rq:
            sigs_pts: list = []
            oks: list[bool] = []
            for i, pf in enumerate(job.prefail):
                if pf:
                    sigs_pts.append(None)
                    oks.append(False)
                    continue
                group_sig = shamir.threshold_aggregate_g2(
                    dict(zip(job.indices[i], job.partials[i]))
                )
                ok = self._oracle_verify_lane(
                    job.group_pks[i], job.msgs[i], group_sig
                )
                sigs_pts.append(group_sig)
                oks.append(ok)
                lanes += 1
            rres.append((sigs_pts, oks))
        mode, cache_n, device_n, python_n = self._decode_breakdown(vq, rq)
        self._account_flush(
            vq,
            rq,
            lanes,
            FlushStats(
                jobs=len(vq) + len(rq),
                lanes=lanes,
                flush_seconds=time.monotonic() - t0,
                window=self._window_current,
                inflight=self._inflight,
                pad_lanes=None,
                padded_lanes=None,
                decode_queue_seconds=self._job_decode_delays(vq, rq),
                fallback=True,
                decode_mode=mode,
                decode_cache_lanes=cache_n,
                decode_device_lanes=device_n,
                decode_python_lanes=python_n,
                decode_spans=self._job_decode_spans(vq, rq),
                decode_hashed=self._job_decode_hashed(vq, rq),
                msg_hash_engine=_msg_hash_engine().name,
                device_span=(w0, time.time()),  # lint: allow(monotonic-clock)
                verify_jobs=len(vq),
                recombine_jobs=len(rq),
                duty_types=self._job_duty_types(vq, rq),
                **self._verify_verdicts(vres),
                parents=self._job_parents(vq, rq),
                tenant_lanes=self._job_tenant_lanes(vq, rq),
            ),
        )
        return vres, rres
