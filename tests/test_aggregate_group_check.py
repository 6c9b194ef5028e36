"""The recombine program checks the GROUP SIGNATURE alone (ISSUE 40): the t
partials of a row were judged once, by the verify program, when they entered
the node, so `step_rlc` / `step_rlc_dec` Lagrange-recombine a row and send
ONE pairing lane a row through the RLC check — what upstream's sigagg and
this repo's plane-less rung (`SigAgg._aggregate_via_tbls`) do.

Held here, on share-index rows that are not 1..t, with the plain reference
(benchmark/reference_threshold.py) the oracle throughout: the served path
(SigAgg -> SlotCoalescer -> the real parsed program, t = 3) against that host
rung, row for row — same aggregate bytes, same verdict a row; one well-formed
forged partial in a row still fails the fast check and the per-lane tier
behind it names the row; the one input on which the group check and a
per-partial check part ways — errors that cancel in the Lagrange sum — is
accepted by both rungs and IS the group signature; for t = 3, 4, 5 the traced
program's Miller batch is 2 x rows (not 2 x rows x (t + 1)), so the lanes
cannot creep back, and the coalescer's recombine path over the program's
host twin equals the host rung; and what a flush says of it
(`FlushStats.pairing_lanes`, `.recombine_attributed`).

The real program (CPU geometry, one device, bucket 4 — the smallest the
plane compiles; MSM off, as every benchmark cell runs it) runs ONCE, in a
fresh process (tests/isolation_util.py), for every case that needs it. One
threshold only: a pairing program is ~3 min of trace, lowering and load on
the CPU whatever `.jax_cache/` holds, t is part of the program, and since
this PR the body reads t in `blsops.threshold_recombine` alone. The file is
named to be collected FIRST: the suite's workers take files in order, so its
minutes run beside the cheap early files and not beside the slot-clock
simnet files near the end of the alphabet, which go red when starved."""

from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import reference as ref  # noqa: E402
from benchmark import reference_threshold as rt  # noqa: E402
from charon_tpu import tbls  # noqa: E402
from charon_tpu.core import cryptoplane as cp  # noqa: E402
from charon_tpu.core import eth2data as d  # noqa: E402
from charon_tpu.core.sigagg import AggregationError, SigAgg  # noqa: E402
from charon_tpu.core.types import Duty, DutyType, pubkey_from_bytes, pubkey_to_bytes  # noqa: E402
from charon_tpu.crypto import g1g2, shamir  # noqa: E402
from charon_tpu.ops import curve as C  # noqa: E402
from tests.isolation_util import (  # noqa: E402
    ISOLATED_HEADER,
    REAL_PROGRAM_LIMIT,
    run_isolated,
)
from tests.test_cryptoplane import FORK, FakePlane, _att_data  # noqa: E402

SLOT = 5
DUTY = Duty(SLOT, DutyType.ATTESTER)
# t -> (n, one share-index row a validator): four rows, the bucket's four
ROWS = {
    3: (4, ((1, 2, 3), (1, 2, 4), (1, 3, 4), (2, 3, 4))),
    4: (7, ((1, 2, 3, 4), (1, 3, 5, 7), (2, 4, 6, 7), (4, 5, 6, 7))),
    5: (7, ((1, 3, 4, 6, 7), (3, 4, 5, 6, 7), (1, 2, 3, 4, 5), (1, 2, 4, 6, 7))),
}
# what is done to ONE row of the t = 3 wave (the others stay honest)
TAMPERED_ROW = 2
MARKER = "GROUP-CHECK "


def wave(t: int, case: str = "honest"):
    """The duty's batch, all of it the plain reference's: per row a
    validator of a seeded n / t cluster signing one attestation with the
    shares its row names. `forged`: one partial of one row is a well-formed
    signature by another secret. `cancelling`: two partials of that row
    carry errors e_a = l_b * D and e_b = -l_a * D (l the row's Lagrange
    coefficients), so neither verifies against its pubshare and their
    recombination is the group signature all the same."""
    n, rows = ROWS[t]
    unsigned = d.SignedData(
        "attestation", d.Attestation(aggregation_bits=(True,), data=_att_data(SLOT)))
    root = unsigned.signing_root(FORK, SLOT // 32)
    batch, pubshares, secrets = {}, {i: {} for i in range(1, n + 1)}, {}
    for v, row in enumerate(rows):
        secret = ref.seeded_scalar("group-check", t, v).to_bytes(32, "big")
        shares = rt.split(secret, n, t, "group-check-split", t, v)
        pk = pubkey_from_bytes(ref.secret_to_public_key(secret))
        sigs = {i: rt.partial_sign(shares[i], root) for i in row}
        if v == TAMPERED_ROW and case == "forged":
            other = ref.seeded_scalar("group-check-forger").to_bytes(32, "big")
            sigs[row[1]] = ref.sign(other, root)
        if v == TAMPERED_ROW and case == "cancelling":
            a, b = row[0], row[1]
            lam = rt.lagrange_at_zero(row)
            delta = g1g2.g2_mul(g1g2.G2_GEN, 0xC0FFEE)
            bump = lambda sig, k: g1g2.g2_to_bytes(
                g1g2.g2_add(g1g2.g2_from_bytes(sig), g1g2.g2_mul(delta, k % ref.R)))
            sigs[a], sigs[b] = bump(sigs[a], lam[b]), bump(sigs[b], -lam[a])
        batch[pk] = [d.ParSignedData(data=unsigned.with_signature(sigs[i]), share_idx=i)
                     for i in row]
        secrets[pk] = secret
        for i in shares:
            pubshares[i][pk] = ref.secret_to_public_key(shares[i])
    return batch, pubshares, secrets, root


def aggregate(agg: SigAgg, batch) -> dict:
    """What SigAgg tells its subscribers of the batch: {pubkey: aggregate
    bytes}, or the pubkeys its AggregationError names."""
    out: dict = {}

    async def on_agg(_duty, data_set):
        out.update({str(pk): signed.signature.hex() for pk, signed in data_set.items()})

    agg.subscribe(on_agg)
    try:
        asyncio.run(agg.aggregate(DUTY, batch))
    except AggregationError as e:
        return {"refused": sorted(str(pk) for pk in batch if str(pk) in str(e))}
    return out


# -- the real program, once, in a process of its own ------------------------------

REAL_T = 3
CASES = ("honest", "cancelling", "forged")


def per_lane_stand_in(plane, batch, pubshares, root):
    """`SlotCryptoPlane._step_dec` stood in for by the host oracle, a pairing
    a lane as the program has it: every partial of a row against its
    pubshare AND the row's group signature (the attribution program is not
    this PR's and costs a second trace; tests/test_mesh.py runs the real
    one). The recombined signatures are the fast program's own."""
    import numpy as np

    verify = cp.SlotCoalescer._oracle_verify_lane
    msg = cp._msg_point(root)

    def step_dec(*args):
        group_sig, _all_ok, row_ok = plane._step_rlc_dec(*args, plane.make_rand(len(batch)))
        sigs = C.g2_unpack(plane.ctx, group_sig)
        ok = [
            bool(decoded)
            and all(verify(g1g2.g1_from_bytes(pubshares[p.share_idx][pk]), msg,
                           g1g2.g2_from_bytes(p.data.signature)) for p in psigs)
            and verify(g1g2.g1_from_bytes(pubkey_to_bytes(pk)), msg, sig)
            for (pk, psigs), sig, decoded in zip(batch.items(), sigs, np.asarray(row_ok))]
        return group_sig, np.asarray(ok), sum(ok)

    return step_dec


def plane_main() -> None:
    """The isolated process's body: the t = 3 waves through SigAgg and the
    coalescer over the real plane, and one JSON line of what came out, which
    programs ran and what each flush said."""
    import jax

    from charon_tpu.ops import msm
    from charon_tpu.parallel import SlotCryptoPlane, make_mesh

    msm.set_msm(False)  # the branch every benchmark cell compiles
    plane = SlotCryptoPlane(make_mesh(jax.devices()[:1]), t=REAL_T)
    programs: list = []
    plane.on_program = lambda family, seconds, lanes: programs.append([family, lanes])
    record: dict = {}
    for case in CASES:
        del programs[:]
        flushes: list = []
        coalescer = cp.SlotCoalescer(
            plane, window=0.05, decode_workers=0, decode_mode="device",
            stats_hook=flushes.append)
        batch, pubshares, _secrets, root = wave(REAL_T, case)
        plane._step_dec = per_lane_stand_in(plane, batch, pubshares, root)
        try:
            out = aggregate(
                SigAgg(threshold=REAL_T, fork=FORK, plane=coalescer,
                       pubshares_by_idx=pubshares),
                batch)
        finally:
            coalescer.close()
        record[case] = {
            "out": out,
            "programs": list(programs),
            "flushes": [
                {"lanes": f.lanes, "pairing_lanes": f.pairing_lanes,
                 "recombine_attributed": f.recombine_attributed, "fallback": f.fallback}
                for f in flushes],
            "recombine_attributed_total": coalescer.flushes_recombine_attributed,
        }
    print(MARKER + json.dumps(record))


# The child of `plane_record` compiles the real `step_rlc_dec` for XLA:CPU,
# and its seconds are the set-up of whichever of the three cases it feeds
# runs first.
real_program = pytest.mark.limit(REAL_PROGRAM_LIMIT)


@pytest.fixture(scope="module")
def plane_record():
    out = run_isolated(
        ISOLATED_HEADER + "import tests.test_aggregate_group_check as t\nt.plane_main()\n",
        MARKER)
    (line,) = [ln for ln in out.splitlines() if ln.startswith(MARKER)]
    return json.loads(line[len(MARKER):])


def host_rung(t: int, case: str) -> dict:
    """The same batch through SigAgg with no plane: `_aggregate_via_tbls`
    on the C++ engine (the python one where the library does not load)."""
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except Exception:  # noqa: BLE001 — no library on this host
        from charon_tpu.tbls.python_impl import PythonImpl

        tbls.set_implementation(PythonImpl())
    batch, _pubshares, _secrets, _root = wave(t, case)
    return aggregate(SigAgg(threshold=t, fork=FORK), batch)


@real_program  # 568 s under six workers, the child's compile (take-up run, ISSUE 41)
def test_the_served_recombination_is_the_host_rungs_row_for_row(plane_record):
    """Non-contiguous rows through `step_rlc_dec`: every aggregate is byte
    for byte the host rung's and the plain reference's group signature, in
    one dispatch of one pairing lane a row."""
    got = plane_record["honest"]
    batch, _pubshares, secrets, root = wave(REAL_T)
    assert got["out"] == host_rung(REAL_T, "honest")
    for pk, psigs in batch.items():
        want = rt.recombine({p.share_idx: p.data.signature for p in psigs})
        assert got["out"][str(pk)] == want.hex() == ref.sign(secrets[pk], root).hex()
    assert got["programs"] == [["mesh/step_rlc_dec", 4]]
    (flush,) = got["flushes"]
    assert flush == {"lanes": 4, "pairing_lanes": 4, "recombine_attributed": False,
                     "fallback": False}


@real_program  # 0.8 s behind the first (take-up run); the child's 568 s where it runs first
def test_a_forged_partial_fails_its_rows_group_check_and_step_dec_names_the_row(plane_record):
    """The forged partial enters the row's group signature under a non-zero
    Lagrange coefficient: the real fast program fails its check, the
    per-lane tier (`step_dec`: stood in for by the host oracle here) is
    dispatched on the same packed rows and refuses that row alone — the
    verdict the host rung gives."""
    got = plane_record["forged"]
    batch, *_ = wave(REAL_T, "forged")
    tampered = str(list(batch)[TAMPERED_ROW])
    assert got["out"] == {"refused": [tampered]} == host_rung(REAL_T, "forged")
    assert got["programs"] == [["mesh/step_rlc_dec", 4], ["mesh/step_dec", 4]]
    (flush,) = got["flushes"]
    assert flush["recombine_attributed"] and flush["pairing_lanes"] == 4
    assert got["recombine_attributed_total"] == 1
    assert plane_record["honest"]["recombine_attributed_total"] == 0


@real_program  # 1.0 s behind the first (take-up run); the child's 568 s where it runs first
def test_errors_that_cancel_in_the_lagrange_sum_give_the_group_signature(plane_record):
    """The one input on which a per-partial check and the group check
    differ (two partials of a row off by errors that cancel): neither
    partial verifies against its pubshare, the recombination IS the group
    signature, and both rungs broadcast it — as upstream would."""
    got = plane_record["cancelling"]
    batch, pubshares, secrets, root = wave(REAL_T, "cancelling")
    pk = list(batch)[TAMPERED_ROW]
    for p in batch[pk][:2]:
        assert not cp.SlotCoalescer._oracle_verify_lane(
            g1g2.g1_from_bytes(pubshares[p.share_idx][pk]), cp._msg_point(root),
            g1g2.g2_from_bytes(p.data.signature))
    assert got["out"] == host_rung(REAL_T, "cancelling")
    assert got["out"][str(pk)] == ref.sign(secrets[pk], root).hex()
    assert got["programs"] == [["mesh/step_rlc_dec", 4]]
    assert not got["flushes"][0]["recombine_attributed"]


# -- the program's shape: one Miller pair of lanes a row ------------------------------


def miller_scan_carries(closed) -> list[list]:
    """The carries' avals of every Miller loop in a traced program: a scan
    over the loop parameter's bits with the conditional add step inside."""
    from charon_tpu.analysis.jaxpr_check import walk_eqns
    from charon_tpu.ops import pairing

    return [
        eqn.params["jaxpr"].in_avals[
            eqn.params["num_consts"]: eqn.params["num_consts"] + eqn.params["num_carry"]]
        for eqn in walk_eqns(closed.jaxpr)
        if eqn.primitive.name == "scan" and eqn.params["length"] == len(pairing.X_BITS)
        and any(inner.primitive.name == "cond" for inner in walk_eqns(eqn.params["jaxpr"].jaxpr))
    ]


@pytest.mark.parametrize("t", sorted(ROWS), ids=lambda t: f"t{t}")
def test_the_step_rlc_programs_miller_batch_is_two_lanes_a_row(monkeypatch, t):
    """Traced, not run: `step_rlc` on 4 rows holds ONE Miller loop (the
    scan over the loop parameter's bits with the conditional add step
    inside) and its carries are [2, rows, limbs] — the group key's pair and
    the generator's, a row each — not [2, rows * (t + 1), ...], whatever t."""
    import jax

    from charon_tpu.crypto.g1g2 import G1_GEN, G2_GEN
    from charon_tpu.ops import msm
    from charon_tpu.parallel import SlotCryptoPlane, make_mesh

    monkeypatch.setattr(msm, "msm_active", lambda: False)
    rows = 4
    plane = SlotCryptoPlane(make_mesh(jax.devices()[:1]), t=t)
    args = plane.pack_inputs(
        [[G1_GEN] * t] * rows, [G2_GEN] * rows, [[G2_GEN] * t] * rows, [G1_GEN] * rows,
        [list(range(1, t + 1))] * rows)
    rand = plane.make_rand(rows)
    assert rand.shape == (rows, plane.fr_ctx.n_limbs)  # one exponent a row
    (carries,) = miller_scan_carries(jax.make_jaxpr(plane._step_rlc)(*args, rand))
    assert carries and {aval.shape[:2] for aval in carries} == {(2, rows)}


# -- the host twin: every threshold, and what a flush says of it ---------------------


class TwoTierPlane(FakePlane):
    """tests/test_cryptoplane.FakePlane with `parallel/mesh`'s two recombine
    tiers and its program hook: every row's group signature at once, and on
    a failure every row alone (the host oracle's pairing, a row)."""

    on_program = None

    def recombine_host(self, pubshares, msgs, partials, group_pks, indices, rng=None):
        sigs = [shamir.threshold_aggregate_g2(dict(zip(idx, row)))
                for idx, row in zip(indices, partials)]
        sound = [cp.SlotCoalescer._oracle_verify_lane(gpk, msg, sig)
                 for gpk, msg, sig in zip(group_pks, msgs, sigs)]
        self.on_program("mesh/step_rlc", 0.0, len(msgs))
        if not all(sound):
            self.on_program("mesh/step", 0.0, len(msgs))
        return sigs, sound


@pytest.mark.parametrize("t", sorted(ROWS), ids=lambda t: f"t{t}")
def test_the_coalescers_recombine_path_is_the_host_rungs_for_every_threshold(t):
    """t = 3, 4, 5 on rows that are not 1..t, SigAgg -> coalescer -> the
    program's host twin (group check alone): the host rung's aggregates,
    the plain reference's, one pairing lane a row."""
    flushes: list = []
    coalescer = cp.SlotCoalescer(TwoTierPlane(t), window=0.005, stats_hook=flushes.append)
    batch, pubshares, secrets, root = wave(t)
    try:
        out = aggregate(
            SigAgg(threshold=t, fork=FORK, plane=coalescer, pubshares_by_idx=pubshares), batch)
    finally:
        coalescer.close()
    assert out == host_rung(t, "honest")
    assert out == {str(pk): ref.sign(secrets[pk], root).hex() for pk in batch}
    assert [(f.pairing_lanes, f.recombine_attributed) for f in flushes] == [(len(batch), False)]


@pytest.mark.parametrize("case", ["honest", "forged"])
def test_a_flush_says_its_pairing_lanes_and_whether_it_fell_to_the_per_lane_program(case):
    """A rehearsed recombine flush (t = 3, four rows): `pairing_lanes` is
    its rows, `recombine_attributed` (and the coalescer's count) set only
    where the plane dispatched `step` / `step_dec` inside it; the next
    flush starts clean."""
    flushes: list = []
    coalescer = cp.SlotCoalescer(TwoTierPlane(3), window=0.005, stats_hook=flushes.append)
    batch, pubshares, *_ = wave(3, case)
    honest, honest_shares, *_ = wave(3)
    try:
        agg = lambda shares: SigAgg(threshold=3, fork=FORK, plane=coalescer, pubshares_by_idx=shares)
        first = aggregate(agg(pubshares), batch)
        second = aggregate(agg(honest_shares), honest)
    finally:
        coalescer.close()
    forged = case == "forged"
    assert ("refused" in first) == forged and "refused" not in second
    assert [f.pairing_lanes for f in flushes] == [4, 4] == [f.lanes for f in flushes]
    assert [f.recombine_attributed for f in flushes] == [forged, False]
    assert coalescer.flushes_recombine_attributed == int(forged)
    assert not any(f.attributed or f.set_resolved for f in flushes)


def test_the_span_and_the_families_carry_them():
    """`pairing_lanes` on `cryptoplane.device`; the node feeds the two
    families from a flush's stats, and the catalogue names them
    (analysis/metrics_check holds docs and code to each other)."""
    from charon_tpu.app import tracer
    from charon_tpu.app.metrics import ClusterMetrics

    t = tracer.Tracer()
    tracer.plane_span_bridge(t)(cp.FlushStats(
        jobs=1, lanes=32, flush_seconds=1.0, window=0.3, inflight=1, pad_lanes=0,
        padded_lanes=32, decode_queue_seconds=(), device_span=(10.0, 11.0),
        recombine_jobs=1, pairing_lanes=32))
    (device,) = [s for s in t.spans if s.name == "cryptoplane.device"]
    assert device.attrs["pairing_lanes"] == 32
    text = (REPO / "charon_tpu/app/run.py").read_text()
    assert "metrics.plane_pairing_lanes" in text
    assert "metrics.plane_flushes_recombine_attributed" in text
    m = ClusterMetrics("hash", "name", "peer")
    m.labels(m.plane_pairing_lanes, "recombine").inc(32)
    m.labels(m.plane_flushes_recombine_attributed).inc()
    out = m.render().decode()
    assert 'tpu_plane_pairing_lanes_total{' in out and 'family="recombine"' in out
    assert "tpu_plane_flushes_recombine_attributed_total" in out
    docs = (REPO / "docs/metrics.md").read_text()
    for name in ("tpu_plane_pairing_lanes_total", "tpu_plane_flushes_recombine_attributed_total"):
        assert f"`{name}`" in docs, name


# -- the Miller pairs a flush's programs ran (ISSUE 42) --------------------------------


def bucketed_plane():
    """tests/test_hostplane.ParsedFakePlane whose packs are padded to a
    bucket of 16, as `parallel/mesh`'s are, and which says how many segments
    its parsed verify program judges."""
    import numpy as np

    from tests.test_hostplane import ParsedFakePlane

    def padded(pack):
        return lambda self, lanes, *rest, **kw: (pack, np.empty(-(-len(lanes) // 16) * 16))

    class BucketedPlane(ParsedFakePlane):
        VERIFY_SETS = 8
        pack_verify_inputs, pack_verify_inputs_parsed = padded("v"), padded("vp")
        pack_inputs = pack_inputs_parsed = padded("r")

    return BucketedPlane(3)


@pytest.mark.parametrize("queue, decode_mode, pairs", [
    ("verify", "device", 16 + 8),  # a pair a lane and one a set's summed signature
    ("verify", "python", 2 * 16),  # the point path's program: two a lane
    ("recombine", "device", 2 * 16),
    ("recombine", "python", 2 * 16),
])
def test_a_flush_says_the_miller_pairs_its_programs_ran(queue, decode_mode, pairs):
    """`FlushStats.miller_pairs` is computed from the BUCKET dispatched:
    bucket + VERIFY_SETS for the parsed verify program, which pairs each
    set's summed signature once, two a lane (a row) of the bucket for the
    point-path verify program and for the recombine programs; `pairing_lanes`
    beside it still reads the lanes judged."""
    from tests.test_hostplane import _sig_items

    flushes: list = []
    coalescer = cp.SlotCoalescer(bucketed_plane(), window=0.005, decode_workers=1,
                                 decode_mode=decode_mode, stats_hook=flushes.append)
    batch, pubshares, _secrets, root = wave(3)
    try:
        if queue == "verify":
            assert asyncio.run(coalescer.verify(_sig_items(3))) == [True] * 3
        else:
            rows = [(pk, sorted(partials, key=lambda p: p.share_idx)) for pk, partials in batch.items()]
            asyncio.run(coalescer.recombine(
                [[pubshares[p.share_idx][pk] for p in row] for pk, row in rows],
                [root] * len(rows),
                [[p.data.signature for p in row] for _pk, row in rows],
                [pubkey_to_bytes(pk) for pk, _row in rows],
                [[p.share_idx for p in row] for _pk, row in rows]))
    finally:
        coalescer.close()
    (flush,) = flushes
    assert flush.miller_pairs == pairs
    assert flush.pairing_lanes == flush.lanes == (3 if queue == "verify" else len(batch))
    assert flush.padded_lanes == 16


def test_a_flush_whose_bucket_the_coalescer_never_saw_counts_no_pairs():
    """A plane without the packed API (the single-stage rung through
    `recombine_host`) packs for itself: `miller_pairs` stays 0 there, and
    `pairing_lanes` still reads the rows."""
    flushes: list = []
    coalescer = cp.SlotCoalescer(TwoTierPlane(3), window=0.005, stats_hook=flushes.append)
    batch, pubshares, *_ = wave(3)
    try:
        aggregate(SigAgg(threshold=3, fork=FORK, plane=coalescer, pubshares_by_idx=pubshares), batch)
    finally:
        coalescer.close()
    assert [(f.miller_pairs, f.pairing_lanes) for f in flushes] == [(0, len(batch))]


def test_the_device_span_and_the_family_carry_the_miller_pairs():
    """`miller_pairs` on `cryptoplane.device`; the node feeds
    `tpu_plane_miller_pairs_total{family}` from a flush's stats beside the
    pairing lanes, and the catalogue names it."""
    from charon_tpu.app import tracer
    from charon_tpu.app.metrics import ClusterMetrics

    t = tracer.Tracer()
    tracer.plane_span_bridge(t)(cp.FlushStats(
        jobs=7, lanes=217, flush_seconds=1.0, window=0.3, inflight=1, pad_lanes=39,
        padded_lanes=256, decode_queue_seconds=(), device_span=(10.0, 11.0),
        verify_jobs=7, pairing_lanes=217, miller_pairs=264))
    (device,) = [s for s in t.spans if s.name == "cryptoplane.device"]
    assert (device.attrs["pairing_lanes"], device.attrs["miller_pairs"]) == (217, 264)
    assert "metrics.plane_miller_pairs" in (REPO / "charon_tpu/app/run.py").read_text()
    m = ClusterMetrics("hash", "name", "peer")
    m.labels(m.plane_miller_pairs, "verify").inc(264)
    out = m.render().decode()
    assert 'tpu_plane_miller_pairs_total{' in out and 'family="verify"' in out
    assert "`tpu_plane_miller_pairs_total`" in (REPO / "docs/metrics.md").read_text()
