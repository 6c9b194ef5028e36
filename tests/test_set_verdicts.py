"""A verdict per SET inside the RLC verify dispatch (ISSUE 36): the parsed
program `verify_rlc_dec` takes its random linear combination per segment —
one segment a partial-signature set (a verify job of the flush) — so a forged
partial fails ITS set, whole, at no further dispatch, and every other set of
the flush passes. Held here to the plain reference
(benchmark/reference_verify.py: a set's verdict is the AND over its lanes),
wherever the forgery sits; the per-lane program behind it for a failing
segment that holds more than one set (more sets than `VERIFY_SETS`, or none
named); what the coalescer says of such a flush (`set_resolved`); and that
the prewarm entry compiles what a live flush dispatches.

The real program (CPU geometry, one device, bucket 4 — the smallest the
plane compiles) runs ONCE, in a fresh process (tests/isolation_util.py), for
every case that needs it; the cases read its record."""

from __future__ import annotations

import asyncio
import functools
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import reference as R, reference_verify as RV  # noqa: E402
from charon_tpu.core import cryptoplane as cp  # noqa: E402
from tests.isolation_util import (  # noqa: E402
    ISOLATED_HEADER,
    REAL_PROGRAM_LIMIT,
    run_isolated,
)
from tests.test_hostplane import ParsedFakePlane  # noqa: E402

SETS = (1, 2, 1)  # lanes of the three sets of a flush: lanes 0 | 1, 2 | 3
# where the forgery sits: (lanes forged, lanes that do not decode, lanes whose
# signatures are swapped round)
CASES = {
    "no-forgery": ((), (), ()),
    "first-lane-of-the-first-set": ((0,), (), ()),
    "last-lane-of-the-last-set": ((3,), (), ()),
    "two-lanes-of-one-set": ((1, 2), (), ()),
    "one-lane-in-each-of-two-sets": ((0, 2), (), ()),
    "a-lane-that-does-not-decode-beside-a-forged-one": ((3,), (2,), ()),
    # each signature genuine, by the other lane's key on the other lane's
    # root: the set's signatures SUM to what its keys signed, so only the
    # lanes' independent exponents refuse it (ISSUE 42)
    "two-genuine-signatures-swapped-inside-one-set": ((), (), (1, 2)),
    # nothing of set 1 reaches its aggregate: the empty sum is the identity,
    # the set's product reads True, its lanes False by their decode mask
    "a-set-whose-every-lane-does-not-decode": ((), (1, 2), ()),
}
MARKER = "SET-VERDICTS "


@functools.cache
def lanes_of(forged=(), undecodable=(), swapped=(), sets=SETS):
    """(pubkey, root, signature) per lane of a flush of `sets`, the plain
    reference's own keys and signatures: a forged lane is a well-formed
    signature by another secret, an undecodable one a flipped byte of x, a
    swapped one carries the genuine signature of the lane before it in
    `swapped` (the first the last's)."""
    out = []
    for i in range(sum(sets)):
        secret = R.seeded_scalar("set-verdicts", i).to_bytes(32, "big")
        root = bytes([i + 1]) * 32
        signer = R.seeded_scalar("forger", i).to_bytes(32, "big") if i in forged else secret
        sig = R.sign(signer, root)
        if i in undecodable:
            sig = sig[:95] + bytes([sig[95] ^ 1])
        out.append((R.secret_to_public_key(secret), root, sig))
    sigs = [out[j][2] for j in swapped[-1:] + swapped[:-1]]
    for i, sig in zip(swapped, sigs):
        out[i] = (*out[i][:2], sig)
    return tuple(out)


def set_of_lane(sets=SETS) -> list[int]:
    return [k for k, lanes in enumerate(sets) for _ in range(lanes)]


reference_verdict = functools.cache(RV.verify)


# -- the real program, once, in a process of its own ----------------------------


def plane_main() -> None:
    """The isolated process's body: every dispatch of the real program this
    file needs, on one plane, and one JSON line of what each answered."""
    import jax

    from charon_tpu.ops import decompress as DEC
    from charon_tpu.parallel import SlotCryptoPlane, make_mesh

    plane = SlotCryptoPlane(make_mesh(jax.devices()[:1]), t=3)
    programs: list[str] = []
    plane.on_program = lambda family, seconds, lanes: programs.append(family)

    def pack(lanes, sets):
        return plane.pack_verify_inputs_parsed(
            [cp._decode_pubkey(pk) for pk, _, _ in lanes],
            [cp._msg_point(root) for _, root, _ in lanes],
            [DEC.parse_g2_lane(sig) for _, _, sig in lanes],
            sets,
        )

    record: dict = {"bucket": plane.bucket_lanes(sum(SETS))}
    # the prewarm entry first: what it compiles is what a flush must find
    (entry,) = [
        run
        for _kind, family, bucket, run in plane.prewarm_programs(
            verify_lanes=(sum(SETS),), recombine_lanes=(), decompress=True)
        if family == "verify_rlc_dec"
    ]
    entry()
    record["programs_after_prewarm"] = plane.jit_cache_size()

    # a live flush: three jobs (three lanes and a padding lane: bucket 4)
    # through the coalescer, which names the sets itself
    coalescer = cp.SlotCoalescer(plane, window=0.05, decode_workers=0, decode_mode="device")
    live = lanes_of(forged=(0,), sets=(1, 1, 1))
    try:
        async def flush():
            return await asyncio.gather(*(coalescer.verify([lane]) for lane in live))

        record["live"] = asyncio.run(flush())
    finally:
        coalescer.close()
    record["programs_after_flush"] = plane.jit_cache_size()
    record["live_programs"], record["live_set_resolved"] = list(programs), coalescer.flushes_set_resolved

    record["cases"] = {}
    for name, where in CASES.items():
        del programs[:]
        lanes = lanes_of(*where)
        oks = plane.verify_packed_parsed(
            pack(lanes, set_of_lane()), plane.make_lane_rand(len(lanes)), len(lanes))
        record["cases"][name] = {"oks": oks, "programs": list(programs)}
    print(MARKER + json.dumps(record))


# The child of `plane_record` compiles the real `verify_rlc_dec` for
# XLA:CPU, and its seconds are the set-up of whichever of the seven cases
# it feeds runs first.
real_program = pytest.mark.limit(REAL_PROGRAM_LIMIT)


@pytest.fixture(scope="module")
def plane_record():
    out = run_isolated(
        ISOLATED_HEADER + "import tests.test_set_verdicts as t\nt.plane_main()\n",
        MARKER)
    (line,) = [ln for ln in out.splitlines() if ln.startswith(MARKER)]
    return json.loads(line[len(MARKER):])


@real_program  # 552 s in [no-forgery] under six workers, the child's compile; 0.1-0.6 s a case behind it (take-up run, ISSUE 41)
@pytest.mark.parametrize("case", CASES)
def test_each_sets_verdict_is_the_references(plane_record, case):
    """Per set, the program's verdict is the plain reference's AND over the
    set's lanes; an honest set passes whole, a set holding a forgery is
    refused whole (None: its lanes are not judged apart), a lane that does
    not decode fails alone (False) — in ONE dispatch."""
    forged, undecodable, swapped = CASES[case]
    got = plane_record["cases"][case]
    lanes, owner = lanes_of(*CASES[case]), set_of_lane()
    sound = [reference_verdict(*lane) for lane in lanes]
    assert sound == [i not in forged + undecodable + swapped for i in range(len(lanes))]
    by_set = lambda oks: [all(ok for ok, s in zip(oks, owner) if s == k) for k in range(len(SETS))]
    assert by_set(got["oks"]) == by_set(sound)
    refused = {owner[i] for i in forged + swapped}
    assert got["oks"] == [False if i in undecodable else None if owner[i] in refused else True
                          for i in range(len(lanes))]
    assert got["programs"] == ["mesh/verify_rlc_dec"]
    assert plane_record["bucket"] == 4


@real_program  # 0.0 s behind the first (take-up run); the child's 552 s where it runs first
def test_the_prewarm_entry_compiles_what_a_live_flush_dispatches(plane_record):
    """Segment ids included: the first flush of a node finds its program
    (the jit cache does not grow), and that flush — three jobs through the
    coalescer, the first one's partial forged, a padding lane behind them —
    is resolved per set."""
    assert plane_record["programs_after_flush"] == plane_record["programs_after_prewarm"] == 1
    assert plane_record["live"] == [[None], [True], [True]]
    assert plane_record["live_programs"] == ["mesh/verify_rlc_dec"]
    assert plane_record["live_set_resolved"] == 1


@pytest.mark.parametrize("devices", (1, 8))
def test_the_programs_miller_batch_is_a_pair_a_lane_and_a_pair_a_set(devices):
    """Traced, not run: `verify_rlc_dec` holds ONE Miller loop (the scan
    over the loop parameter's bits with the conditional add step inside)
    and its carries are [lanes + VERIFY_SETS, limbs] — lane i's (r_i * pk_i,
    H(m_i)) and set s's (-G1, S_s), the signature side summed in G2 — not
    [2, lanes, limbs] (ISSUE 42). On one device at the four lanes of the
    cases above; on the eight-device mesh at the bucket of a lone
    submission, ONE lane a shard: the smallest body a node compiles (its
    prewarm's first shape), each shard pairing its own lane and its own
    eight sums."""
    import jax

    from charon_tpu.crypto.g1g2 import G1_GEN, G2_GEN
    from charon_tpu.ops import decompress as DEC
    from charon_tpu.parallel import SlotCryptoPlane, make_mesh
    from tests.test_aggregate_group_check import miller_scan_carries

    plane = SlotCryptoPlane(make_mesh(jax.devices()[:devices]), t=3)
    lanes = sum(SETS) if devices == 1 else 1
    a_shard = plane.bucket_lanes(lanes) // devices
    assert a_shard == (4 if devices == 1 else 1)
    sets, *arrays = plane.pack_verify_inputs_parsed(
        [G1_GEN] * lanes, [G2_GEN] * lanes,
        [DEC.parse_g2_lane(lanes_of()[0][2])] * lanes, set_of_lane()[:lanes])
    (carries,) = miller_scan_carries(jax.make_jaxpr(plane._verify_rlc_dec)(
        *arrays, plane.make_lane_rand(lanes), sets.seg))
    assert carries and {aval.shape[:-1] for aval in carries} == {(a_shard + plane.VERIFY_SETS,)}


@pytest.mark.parametrize("lanes", (1, 2, 32, 128))
def test_each_sets_sum_is_the_plain_sum_of_its_lanes(lanes):
    """No pairing: the fold `batched_verify_rlc_sets` takes its S_s by —
    `_point_sum_scan` at the kernel tile over the identity-masked [lanes,
    sets] grid — against plain affine adds, set by set. 128 lanes of 8
    sets are four slices of a tile: three steps add them into the first,
    five fold it; 32 lanes are one slice (five folds), 1 and 2 a shard's share of a small flush (no step, one). The
    points repeat four lanes apart inside one set, so a step adds a point
    to ITSELF (the complete add's doubling case), every fourth lane is
    the identity (an exponent of 0), and the last set is empty."""
    import jax
    import jax.numpy as jnp

    from charon_tpu.crypto import g1g2 as REF
    from charon_tpu.ops import curve as C, limb, pairing as DP
    from charon_tpu.ops.pallas_mont import TILE

    n_sets = 8  # the program's own: SlotCryptoPlane.VERIFY_SETS
    multiples = [REF.g2_mul(REF.G2_GEN, k) for k in (5, 7, 11)]
    points = [None if i % 4 == 3 else multiples[i % 4] for i in range(lanes)]
    seg = [(i // 4) % (n_sets - 1) if i % 4 != 1 else 0 for i in range(lanes)]
    g2f = C.g2_ops(limb.FP)

    @jax.jit
    def sums(aff, seg):
        in_set = seg[:, None] == jnp.arange(n_sets, dtype=seg.dtype)[None, :]
        grid = jax.tree_util.tree_map(
            lambda a, o: jnp.where(in_set[..., None], a[:, None, :], o),
            C.affine_to_point(g2f, aff), C.point_identity(g2f, (n_sets,)))
        return C.point_to_affine(
            g2f, DP._point_sum_scan(C, g2f, grid, lanes, TILE))

    want = [None] * n_sets
    for point, s in zip(points, seg):
        want[s] = REF.g2_add(want[s], point)
    assert want[-1] is None and (lanes < 32 or None not in want[:-1])
    assert C.g2_unpack(limb.FP, sums(C.g2_pack(limb.FP, points), jnp.asarray(seg, jnp.int32))) == want


# -- the fold and the tier behind it: the host's half, the programs stood in for --


@pytest.fixture
def stub_plane(monkeypatch):
    """A real SlotCryptoPlane (its pack, its fold, its choice of tier) whose
    two parsed verify programs are stood in for by the plain reference: what
    the RLC program answers per segment, what the per-lane program answers
    per lane. The programs' own verdicts are the cases above."""
    import jax
    import numpy as np

    from charon_tpu.ops import decompress as DEC
    from charon_tpu.parallel import SlotCryptoPlane, make_mesh

    plane = SlotCryptoPlane(make_mesh(jax.devices()[:1]), t=3)
    plane.programs = []
    plane.on_program = lambda family, seconds, lanes: plane.programs.append(family)

    def serve(lanes, sets):
        sound = np.array([reference_verdict(*lane) for lane in lanes])
        pad = plane.bucket_lanes(len(lanes)) - len(lanes)
        sound = np.concatenate((sound, np.ones(pad, bool)))

        def rlc(pk, msg, sx0, sx1, sign, live, rand, seg):
            assert seg.dtype == np.int32 and seg.shape == sound.shape
            return (np.array([sound[seg == s].all() for s in range(plane.VERIFY_SETS)]),
                    np.asarray(live))

        monkeypatch.setattr(plane, "_verify_rlc_dec", rlc)
        monkeypatch.setattr(
            plane, "_verify_dec", lambda pk, msg, sx0, sx1, sign, live: sound & np.asarray(live))
        arrays = plane.pack_verify_inputs_parsed(
            [cp._decode_pubkey(pk) for pk, _, _ in lanes],
            [cp._msg_point(root) for _, root, _ in lanes],
            [DEC.parse_g2_lane(sig) for _, _, sig in lanes],
            sets)
        return arrays[0], plane.verify_packed_parsed(arrays, None, len(lanes))

    plane.serve = serve
    return plane


def test_more_jobs_than_segments_fall_to_the_per_lane_tier(stub_plane):
    """Nine one-lane jobs over eight segments: the first two share a segment,
    and a forgery THERE cannot be billed to a set — the per-lane program
    answers, lane for lane as before; a forgery in a segment of its own is
    still resolved by the RLC tier."""
    sets = (1,) * 9
    assert len(sets) > stub_plane.VERIFY_SETS
    folded, oks = stub_plane.serve(lanes_of(forged=(1,), sets=sets), set_of_lane(sets))
    assert folded.seg[:9].tolist() == [0, 0, 1, 2, 3, 4, 5, 6, 7]
    assert folded.mixed.tolist() == [True] + [False] * 7
    assert oks == [i != 1 for i in range(9)]
    assert stub_plane.programs == ["mesh/verify_rlc_dec", "mesh/verify_dec"]

    del stub_plane.programs[:]
    _, oks = stub_plane.serve(lanes_of(forged=(5,), sets=sets), set_of_lane(sets))
    assert oks == [None if i == 5 else True for i in range(9)]
    assert stub_plane.programs == ["mesh/verify_rlc_dec"]


def test_a_call_naming_no_sets_is_the_two_tier_behaviour(stub_plane):
    """No sets named (the warm-up, a caller outside the coalescer): one
    segment, judged as a whole — a passing batch is one dispatch, a failing
    one is re-dispatched through the per-lane program and answered per
    lane, the honest lanes of the forger's set True."""
    folded, oks = stub_plane.serve(lanes_of(), None)
    assert not folded.seg.any() and folded.mixed.tolist() == [True] + [False] * 7
    assert oks == [True] * 4 and stub_plane.programs == ["mesh/verify_rlc_dec"]

    del stub_plane.programs[:]
    _, oks = stub_plane.serve(lanes_of(forged=(1,)), None)
    assert oks == [True, False, True, True]
    assert stub_plane.programs == ["mesh/verify_rlc_dec", "mesh/verify_dec"]


# -- what the coalescer says of a set-resolved flush ----------------------------


class SetwisePlane(ParsedFakePlane):
    """ParsedFakePlane whose parsed verify answers per named set, as
    `parallel/mesh` does, and announces its dispatches: a lane is None iff
    its set holds a signature on the `forged` list."""

    def __init__(self, t: int, forged):
        super().__init__(t)
        self.forged, self.on_program = set(forged), None
        self.programs: list[str] = []

    def pack_verify_inputs_parsed(self, pks, msgs, parsed, sets=None):
        return [(s, p.raw) for s, p in zip(sets, parsed)], *super().pack_verify_inputs_parsed(
            pks, msgs, parsed)

    def verify_packed_parsed(self, arrays, rand, n: int):
        self.programs.append("verify_rlc_dec")
        self.on_program("mesh/verify_rlc_dec", 0.0, n)
        refused = {s for s, raw in arrays[0] if raw in self.forged}
        return [None if s in refused else True for s, _ in arrays[0]]


def test_the_coalescer_says_set_resolved_and_dispatches_nothing_more():
    stats = []
    jobs = [lanes_of(forged=(1,))[0:1], lanes_of(forged=(1,))[1:3], lanes_of()[3:4]]
    plane = SetwisePlane(3, forged=[jobs[1][0][2]])
    coalescer = cp.SlotCoalescer(plane, window=0.05, decode_workers=0,
                                 decode_mode="device", stats_hook=stats.append)
    try:
        async def flush():
            return await asyncio.gather(*(coalescer.verify(list(job)) for job in jobs))

        assert asyncio.run(flush()) == [[True], [None, None], [True]]
    finally:
        coalescer.close()
    (flush_stats,) = stats
    assert flush_stats.set_resolved and not flush_stats.attributed
    assert (flush_stats.sets_invalid, flush_stats.lanes_invalid) == (1, 2)
    assert flush_stats.attribute_span is None and flush_stats.attribute_lanes == 0
    assert plane.programs == ["verify_rlc_dec"]  # no mesh/verify_dec
    assert (coalescer.flushes_set_resolved, coalescer.flushes_attributed) == (1, 0)
    assert coalescer.lanes_invalid == 2


def test_a_set_refused_whole_is_one_bad_lane_to_the_tenants_breaker():
    """The tenant service counts the lanes KNOWN bad: of a set refused whole
    (None a lane: not judged apart) that is one, as it was when the per-lane
    tier named the forged partial — so one forging peer does not quarantine
    the node it sends to, whichever of its sets completes first; 32 lanes
    each known bad still open the breaker."""
    from charon_tpu.core.cryptosvc import CryptoPlaneService, TenantQuota
    from tests.test_hostplane import StubCoalescer

    class Verbatim(StubCoalescer):
        async def verify(self, items, deadline=None, tenant=None):
            return list(items)

    async def serve(*sets):
        svc = CryptoPlaneService(Verbatim(), round_interval=0.001)
        plane = svc.register("node", TenantQuota())
        try:
            for lanes in sets:
                assert await plane.verify(lanes) == lanes
            return svc.tenant("node")
        finally:
            svc.close()

    tenant = asyncio.run(serve([True] * 32, [None] * 32))
    assert tenant.breaker.state == "closed"
    assert (tenant.completed_lanes, tenant.failed_lanes) == (32, 1)
    assert asyncio.run(serve([True] * 32, [False] * 32)).breaker.state == "open"
