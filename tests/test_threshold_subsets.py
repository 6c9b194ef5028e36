"""The served recombination against the plain threshold reference on share
index sets that are NOT 1..t (ISSUE 28): a cluster with operators down
aggregates from whichever t shares exist, and the warm-up, the prewarm and
both healthy benchmark cells only ever recombine the first t.

Key material, partial signatures and the answers are benchmark/
reference_threshold.py's (plain Python, imports benchmark/reference.py
alone); under test are SigAgg's row assembly, the coalescer's recombine
path over the counting host plane (tests/test_cryptoplane.FakePlane: the
program's own Lagrange recombination, no device), the plane-less tbls rung
on the native engine, ParSigDB's emission rule, and the one part of the
device's recombine program that sees a share index — the Lagrange
coefficients, at the chip's u32 limb geometry."""

from __future__ import annotations

import asyncio
import itertools
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import reference as ref  # noqa: E402
from benchmark import reference_threshold as rt  # noqa: E402
from charon_tpu import tbls  # noqa: E402
from charon_tpu.core import eth2data as d  # noqa: E402
from charon_tpu.core.cryptoplane import SlotCoalescer  # noqa: E402
from charon_tpu.core.parsigdb import ParSigDB  # noqa: E402
from charon_tpu.core.sigagg import AggregationError, SigAgg  # noqa: E402
from charon_tpu.core.types import Duty, DutyType, pubkey_from_bytes  # noqa: E402
from tests.test_cryptoplane import FORK, FakePlane, _att_data  # noqa: E402

SLOT = 5
DUTY = Duty(SLOT, DutyType.ATTESTER)
# n = 4, t = 3: every 3-subset; n = 7, t = 5: the benchmark cell's speakers,
# the last five, and the first five (what the warm-up dispatches)
SUBSETS = [(4, 3, s) for s in itertools.combinations(range(1, 5), 3)] + [
    (7, 5, (1, 3, 4, 6, 7)), (7, 5, (3, 4, 5, 6, 7)), (7, 5, (1, 2, 3, 4, 5))]
IDS = ["%dof%d-%s" % (t, n, "".join(map(str, s))) for n, t, s in SUBSETS]


def _validator(n: int, t: int, v: int = 0):
    """One validator of a seeded n / t cluster, all of it the reference's:
    (group secret, shares, PubKey, unsigned attestation, signing root)."""
    secret = ref.seeded_scalar("subsets-group", n, t, v).to_bytes(32, "big")
    shares = rt.split(secret, n, t, "subsets-split", n, t, v)
    pk = pubkey_from_bytes(ref.secret_to_public_key(secret))
    unsigned = d.SignedData(
        "attestation", d.Attestation(aggregation_bits=(True,), data=_att_data(SLOT)))
    return secret, shares, pk, unsigned, unsigned.signing_root(FORK, SLOT // 32)


def _partials(shares, unsigned, root, subset):
    return [d.ParSignedData(data=unsigned.with_signature(rt.partial_sign(shares[i], root)),
                            share_idx=i) for i in subset]


def _aggregate(agg: SigAgg, batch) -> dict:
    out: dict = {}

    async def on_agg(_duty, data_set):
        out.update(data_set)

    agg.subscribe(on_agg)
    asyncio.run(agg.aggregate(DUTY, batch))
    return out


@pytest.mark.parametrize("n,t,subset", SUBSETS, ids=IDS)
def test_sigagg_through_the_coalescer_equals_the_plain_recombination(n, t, subset):
    secret, shares, pk, unsigned, root = _validator(n, t)
    psigs = _partials(shares, unsigned, root, subset)
    fake = FakePlane(t)
    plane = SlotCoalescer(fake, window=0.005)
    agg = SigAgg(
        threshold=t, fork=FORK, plane=plane,
        pubshares_by_idx={i: {pk: ref.secret_to_public_key(shares[i])} for i in shares})
    try:
        out = _aggregate(agg, {pk: psigs})
    finally:
        plane.close()
    assert fake.recombine_calls == 1 and fake.recombine_lane_count == 1
    want = rt.recombine({p.share_idx: p.data.signature for p in psigs})
    assert out[pk].signature == want == ref.sign(secret, root)


@pytest.mark.parametrize("n,t,subset", SUBSETS, ids=IDS)
def test_the_tbls_rung_equals_the_plain_recombination(n, t, subset):
    """No plane: SigAgg's one-duty batch on the process's tbls (the C++
    engine a host-only node runs, and the rung a shed job lands on)."""
    native_impl = pytest.importorskip("charon_tpu.tbls.native_impl")
    try:
        tbls.set_implementation(native_impl.NativeImpl())
    except Exception as e:  # noqa: BLE001 — no library on this host
        pytest.skip(f"native tbls engine unavailable: {e}")
    secret, shares, pk, unsigned, root = _validator(n, t, v=1)
    psigs = _partials(shares, unsigned, root, subset)
    out = _aggregate(SigAgg(threshold=t, fork=FORK), {pk: psigs})
    want = rt.recombine({p.share_idx: p.data.signature for p in psigs})
    assert out[pk].signature == want == ref.sign(secret, root)


@pytest.mark.parametrize("n,t", [(4, 3), (7, 5)], ids=["3of4", "5of7"])
def test_one_partial_short_never_aggregates(n, t):
    """t - 1 partials: ParSigDB emits nothing, SigAgg refuses the batch
    if handed it, and what a recombination of t - 1 WOULD give is not
    the group signature (so the refusal is no formality)."""
    secret, shares, pk, unsigned, root = _validator(n, t, v=2)
    short = _partials(shares, unsigned, root, tuple(range(2, t + 1)))
    db = ParSigDB(threshold=t)
    emitted = []

    async def on_threshold(_duty, batch):
        emitted.append(batch)

    db.subscribe_threshold(on_threshold)

    async def store():
        for p in short:
            await db.store_external(DUTY, {pk: p})

    asyncio.run(store())
    assert emitted == [] and len(db._store[(DUTY, pk)]) == t - 1
    with pytest.raises(AggregationError, match="insufficient partial signatures"):
        asyncio.run(SigAgg(threshold=t, fork=FORK).aggregate(DUTY, {pk: short}))
    assert rt.recombine({p.share_idx: p.data.signature for p in short}) != ref.sign(secret, root)
    # the t-th brings the batch out, whichever index it carries
    last = _partials(shares, unsigned, root, (n,))[0]
    asyncio.run(db.store_external(DUTY, {pk: last}))
    assert [sorted(p.share_idx for p in b[pk]) for b in emitted] == [[*range(2, t + 1), n]]


def test_the_reference_recombines_any_t_shares_and_only_t():
    secret = ref.seeded_scalar("subsets-secret").to_bytes(32, "big")
    shares = rt.split(secret, 7, 5, "subsets-poly")
    for subset in itertools.combinations(range(1, 8), 5):
        assert rt.recombine_secret({i: shares[i] for i in subset}) == secret
    assert rt.recombine_secret({i: shares[i] for i in (1, 3, 4, 6)}) != secret
    with pytest.raises(ref.ReferenceError_):
        rt.lagrange_at_zero([1, 3, 3])
    point = rt.g2_decompress(ref.sign(secret, b"m"))
    assert ref.on_g2(point) and ref.g2_compress(point) == ref.sign(secret, b"m")


@pytest.mark.parametrize("t,rows", [
    (3, [list(s) for s in itertools.combinations(range(1, 5), 3)]),
    (5, [[1, 3, 4, 6, 7], [3, 4, 5, 6, 7], [1, 2, 3, 4, 5]]),
], ids=["t3", "t5"])
def test_device_lagrange_coefficients_at_the_chip_geometry(t, rows):
    """`step_rlc_dec` takes the share indices as an int32 [V, t] array and
    works the coefficients out on the device (ops/blsops.
    lagrange_coeffs_at_zero): the only part of the program that sees an
    index. Here that graph alone, jitted on the CPU at the u32 Fr
    geometry the chip runs (22 limbs of 12 bits), against the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from charon_tpu.ops import blsops, limb

    ctx = limb.FR32
    coeffs = jax.jit(lambda idx: blsops.lagrange_coeffs_at_zero(ctx, idx, t))
    out = np.asarray(coeffs(jnp.asarray(np.asarray(rows, np.int32))))
    for row, limbs in zip(rows, out):
        want = rt.lagrange_at_zero(row)
        got = [sum(int(x) << (ctx.limb_bits * k) for k, x in enumerate(lane)) for lane in limbs]
        assert got == [want[i] for i in row]
