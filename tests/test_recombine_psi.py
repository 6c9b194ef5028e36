"""The recombine program's Lagrange multiplication over psi (ISSUE 46):
each partial is multiplied over the four bases (P, -psi P, psi^2 P,
-psi^3 P) with its coefficient cut in base |x|, 64 joint steps in place of
255 (ops/curve.g2_scalar_mul_psi, called by ops/blsops.threshold_recombine).

Held here, on the CPU at a handful of lanes: the digit cut against Python
integers at both limb geometries; the multiplication against crypto/g1g2;
the recombination against crypto/shamir on share-index rows that are not
1..t (the byz and node-down cells' rows); the traced routine's shape (one
scan of 64 steps over the flat lanes, none of 255); and the guard of the
form's precondition — a point of the twist outside G2 leaves
decompress_g2_graph as an invalid identity lane."""

from __future__ import annotations

import functools
import itertools
import random

import pytest

from charon_tpu.crypto import g1g2, shamir
from charon_tpu.crypto.fields import R, X_ABS

LANES = 16
_RNG = random.Random(46)

EDGES = [
    0, 1, 2, X_ABS - 1, X_ABS, X_ABS + 1, X_ABS**2 - 1, X_ABS**2,
    X_ABS**3 - 1, X_ABS**3, X_ABS**3 + X_ABS, R - X_ABS**2, R - 2, R - 1,
    (X_ABS - 1) * (1 + X_ABS + X_ABS**2), (1 << 254) % R,
]
# every index subset tests/test_threshold_subsets.py walks
SUBSETS = [list(s) for s in itertools.combinations(range(1, 5), 3)] + [
    [1, 3, 4, 6, 7], [3, 4, 5, 6, 7], [1, 2, 3, 4, 5]]
LAGRANGE = [c for s in SUBSETS for c in shamir.lagrange_coeffs_at_zero(s).values()]
RANDOM = [_RNG.randrange(R) for _ in range(64)]
SCALARS = {"edges": EDGES, "lagrange": LAGRANGE, "random": RANDOM}


def _ctxs(geometry: str):
    from charon_tpu.ops import limb

    return (limb.FP, limb.FR) if geometry == "u64" else (limb.FP32, limb.FR32)


@pytest.mark.parametrize("geometry", ["u64", "u32"])
@pytest.mark.parametrize("group", list(SCALARS))
def test_digit_cut_is_the_base_x_expansion(group, geometry):
    """sum d_i |x|^i == k exactly, every d_i under |x| (< 2^64): the four
    base-|x| digits, by reciprocal multiplication with no correction."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from charon_tpu.ops import curve as C
    from charon_tpu.ops import limb

    _, fr = _ctxs(geometry)
    ks = SCALARS[group]
    digits = np.asarray(
        jax.jit(functools.partial(C.psi_digits, fr))(jnp.asarray(limb.ctx_pack(fr, ks)))
    )
    assert digits.shape == (4, len(ks), fr.n_limbs)
    for lane, k in enumerate(ks):
        ds = [limb.ctx_unpack(fr, digits[i, lane][None])[0] for i in range(4)]
        assert sum(d * X_ABS**i for i, d in enumerate(ds)) == k, (k, ds)
        assert all(0 <= d < X_ABS for d in ds), (k, ds)


def test_digit_cut_reciprocals_are_exact_to_the_last_scalar():
    """The reciprocals' claim, in Python integers: floor(k m / 2^s) is
    floor(k / |x|^i) wherever the estimate could be off by one — at the
    multiples of the divisor and one below them, up to 2^255."""
    from charon_tpu.ops import curve as C
    from charon_tpu.ops import limb

    shifts, recips, _x = C._psi_cut_consts(limb.FR32)
    for i, (s, m_limbs) in enumerate(zip(shifts, recips), start=1):
        d = X_ABS**i
        m = limb.unpack(m_limbs[None], limb.FR32.limb_bits)[0]
        top = (1 << 255) // d
        for q in [1, 2, top // 2, top - 1, top] + [_RNG.randrange(1, top) for _ in range(200)]:
            for k in (q * d - 1, q * d, q * d + 1):
                if k < 1 << 255:
                    assert (k * m) >> s == k // d, (i, k)
        assert ((1 << 255) - 1) * m >> s == ((1 << 255) - 1) // d


@functools.lru_cache(maxsize=None)
def _mul_kernel(geometry: str):
    import jax

    from charon_tpu.ops import curve as C

    ctx, fr = _ctxs(geometry)
    f = C.g2_ops(ctx)
    return jax.jit(
        lambda aff, ks: C.point_to_affine(f, C.g2_scalar_mul_psi(ctx, fr, aff, ks))
    )


def _device_mul(geometry: str, pts, ks):
    from charon_tpu.ops import curve as C

    ctx, fr = _ctxs(geometry)
    assert len(pts) == len(ks) == LANES  # one shape, one compile a geometry
    return C.g2_unpack(ctx, _mul_kernel(geometry)(C.g2_pack(ctx, pts), C.fr_pack(fr, ks)))


def _rand_g2():
    return g1g2.g2_mul(g1g2.G2_GEN, _RNG.randrange(1, R))


def _batches(ks):
    ks = list(ks) + RANDOM[: -len(ks) % LANES]
    return [ks[i : i + LANES] for i in range(0, len(ks), LANES)]


@pytest.mark.parametrize("group", list(SCALARS))
def test_four_base_multiplication_equals_the_plain_ladder(group):
    """[k]P for every scalar of the group on random G2 points, against
    crypto/g1g2's own ladder."""
    for ks in _batches(SCALARS[group]):
        pts = [_rand_g2() for _ in ks]
        got = _device_mul("u64", pts, ks)
        for p, k, out in zip(pts, ks, got):
            assert out == g1g2.g2_mul(p, k), k


def test_four_base_multiplication_identity_lanes_and_zero_scalars():
    """The (0, 0) identity lane stays the identity under any scalar (its
    four bases are the identity: the table's sixteen entries too), k = 0
    takes any point there, and [r - 1]P = -P."""
    p = _rand_g2()
    pts = [None, None, None, p, p, p] + [_rand_g2() for _ in range(LANES - 6)]
    ks = [0, 1, R - 1, 0, 1, R - 1] + RANDOM[: LANES - 6]
    got = _device_mul("u64", pts, ks)
    assert got[:6] == [None, None, None, None, p, g1g2.g2_neg(p)]
    for q, k, out in zip(pts[6:], ks[6:], got[6:]):
        assert out == g1g2.g2_mul(q, k)


def test_four_base_multiplication_at_the_chip_geometry():
    """The same graph at the u32 geometry the chip runs (32 / 22 limbs of 12
    bits; the multiplications on the XLA path, no Pallas on the CPU)."""
    ks = EDGES[3:11] + RANDOM[:8]
    pts = [_rand_g2() for _ in ks]
    for p, k, out in zip(pts, ks, _device_mul("u32", pts, ks)):
        assert out == g1g2.g2_mul(p, k), k


ROWS = {  # share-index rows that are not 1..t: the cells' own among them
    3: [[1, 3, 4], [2, 3, 4], [1, 2, 4], [1, 2, 3]],
    4: [[1, 3, 4, 6], [2, 4, 5, 7], [1, 2, 3, 4], [4, 5, 6, 7]],
    5: [[1, 3, 4, 6, 7], [3, 4, 5, 6, 7], [1, 2, 3, 4, 5], [1, 2, 4, 5, 7]],
}


@pytest.fixture
def per_lane_branch():
    """What every benchmark cell compiles (CHARON_MSM=0): the branch of
    threshold_recombine that multiplies lane by lane. The flag is read at
    trace time, so the cached kernels go with it, both ways."""
    from charon_tpu.ops import blsops
    from charon_tpu.ops import msm as MSM

    before = MSM._MSM_MODE
    MSM.set_msm(False)
    blsops.clear_kernel_caches()
    yield
    MSM.set_msm(before)
    blsops.clear_kernel_caches()


@pytest.mark.parametrize("t", sorted(ROWS))
def test_threshold_recombine_equals_shamir_on_non_contiguous_rows(t, per_lane_branch):
    """ops/blsops.threshold_recombine (what step_rlc_dec and the tbls
    engine run) against crypto/shamir.threshold_aggregate_g2, row by row:
    a group secret a row, its shares' signatures of one message point, the
    aggregate the secret's own signature."""
    from charon_tpu.ops import blsops

    msg = _rand_g2()
    batch, want = [], []
    for row in ROWS[t]:
        shares = shamir.split(_RNG.randrange(1, R), 7, t)
        partials = {i: g1g2.g2_mul(msg, shares[i]) for i in row}
        batch.append(partials)
        want.append(shamir.threshold_aggregate_g2(partials))
        assert want[-1] == g1g2.g2_mul(msg, shamir.recover_secret({i: shares[i] for i in row}))
    assert blsops.default_engine().threshold_aggregate_batch(batch) == want


@pytest.mark.parametrize("t", [3, 4, 5])
def test_traced_recombine_walks_64_joint_steps_not_255(t, per_lane_branch):
    """The mechanism is unconditional, so there is no hit share to count:
    the traced routine holds ONE scan whose carry is a projective G2 point
    on the rows * t flat lanes, it has 64 steps, and no scan of the
    routine has 255 (no trace of the single-base ladder)."""
    import jax
    import jax.numpy as jnp

    from charon_tpu.analysis.jaxpr_check import walk_eqns
    from charon_tpu.ops import blsops, limb

    rows, ctx, fr = 4, limb.FP32, limb.FR32
    coord = jax.ShapeDtypeStruct((rows, t, ctx.n_limbs), jnp.uint32)
    sig = ((coord, coord), (coord, coord))
    idx = jax.ShapeDtypeStruct((rows, t), jnp.int32)
    traced = jax.make_jaxpr(
        lambda s, i: blsops.threshold_recombine(ctx, fr, t, s, i)
    )(sig, idx)
    scans = [e for e in walk_eqns(traced.jaxpr) if e.primitive.name == "scan"]
    lengths = [e.params["length"] for e in scans]
    assert 255 not in lengths and lengths.count(64) == 1, lengths

    def carries_flat_point(eqn):
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        shapes = [v.aval.shape for v in eqn.invars[n_consts : n_consts + n_carry]]
        return shapes == [(rows * t, ctx.n_limbs)] * 6

    assert [e.params["length"] for e in scans if carries_flat_point(e)] == [64]
    # and the rest of the routine is what it was: the table's 11 adds, psi
    # three times, the fold over t, the two inversions
    assert sorted(lengths) == sorted(
        [3, 11, 64, t - 1, fr.modulus.bit_length() - 1, ctx.modulus.bit_length() - 1]
    ), lengths


@pytest.mark.filterwarnings("ignore")
def test_a_twist_point_outside_g2_never_reaches_the_multiplication():
    """The precondition's guard: psi(P) = [x]P holds on G2 alone, and the
    program's decompression (ops/decompress.decompress_g2_graph, the psi
    subgroup check) hands a point of the twist outside G2 on as an INVALID
    lane holding the (0, 0) identity — its row's `row_ok` goes off in
    step_rlc_dec and the multiplication sees the identity."""
    import numpy as np

    from charon_tpu.ops import blsops
    from charon_tpu.ops import decompress as DEC
    from tests.test_decompress import _g2_on_curve_not_in_subgroup

    stray = _g2_on_curve_not_in_subgroup()
    assert g1g2.g2_is_on_curve(stray) and not g1g2.g2_in_subgroup(stray)
    assert g1g2.g2_psi(stray) != g1g2.g2_neg(g1g2.g2_mul_raw(stray, X_ABS))
    good = _rand_g2()
    eng = blsops.default_engine()
    # 17 lanes: the bucket of tests/test_decompress.py's battery, so the
    # two files share one compiled program
    encs = [g1g2.g2_to_bytes(stray)] + [g1g2.g2_to_bytes(good)] * 16
    pts, valid = eng.decompress_g2_batch(encs)
    assert valid == [False] + [True] * 16 and pts == [None] + [good] * 16
    # the kernel's own outputs, as the step program reads them: the lane's
    # affine limbs are all zero, which affine_to_point takes for the identity
    parsed = [DEC.parse_g2_lane(e) for e in encs]
    parsed += [parsed[0]] * (blsops.bucket_lanes(len(parsed)) - len(parsed))
    aff, ok = blsops._decompress_g2_kernel(eng.ctx, eng.fr_ctx, True)(
        *DEC.pack_parsed_g2(eng.ctx, parsed)
    )
    assert not np.asarray(ok)[0] and np.asarray(ok)[1:17].all()
    assert all(not np.asarray(c)[0].any() for xy in aff for c in xy)
