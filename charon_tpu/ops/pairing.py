"""Batched optimal-ate pairing for BLS12-381 on the limb engine.

Device-side counterpart of charon_tpu/crypto/pairing_fast.py (the validated
scalar specification): projective Miller loop with unnormalized sparse
lines, and an x-chain final exponentiation computing f^(3h) via the BLS12
lattice identity — sound for every product-of-pairings == 1 check.

Batch semantics: every function maps over arbitrary leading batch axes. A
"pair" is (p, q) with p a batched affine G1 point (Fp limb pair) and q a
batched affine G2 point (Fp2 pair). Identity lanes (encoded affine (0, 0))
contribute the neutral line, so e(identity, q) == 1 per lane — matching the
aggregate-verify semantics the workflow needs.

Control flow is XLA-friendly: the Miller loop is a lax.scan over the static
64-bit BLS parameter schedule with lax.cond for the sparse add steps (only
6 of 63 bits are set), and the final exponentiation's x-chains are scans
with Granger–Scott cyclotomic squarings.

Replaces (batched) what the reference does one-signature-at-a-time through
herumi's pairing (ref: tbls/herumi.go:288 Verify, tbls/herumi.go:318
VerifyAggregate).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from charon_tpu.crypto.fields import P, X_ABS, X_IS_NEG
from charon_tpu.crypto import g1g2 as REF
from charon_tpu.ops import curve as C
from charon_tpu.ops import fptower as T
from charon_tpu.ops import limb
from charon_tpu.ops.limb import ModCtx

# Miller-loop schedule: bits of |x| below the leading one, MSB first.
X_BITS = np.array([int(b) for b in bin(X_ABS)[3:]], np.uint8)
# Full bit string of |x| (used by the cyclotomic x-powers).
X_BITS_FULL = np.array([int(b) for b in bin(X_ABS)[2:]], np.uint8)


# ---------------------------------------------------------------------------
# Sparse line multiplication: f * (l0 + l1 v w + l2 v^2 w)
# ---------------------------------------------------------------------------


def fp12_mul_sparse_line(ctx, f, l0, l1, l2):
    """18 fp2 muls vs 36 for a dense fp12 mul (spec: pairing_fast.py:79) —
    all independent, executed as ONE stacked base mul; the combine runs in
    three stacked add levels (sums, xi twists, final adds):
        c0 = (p0 + xi(p7+p8), p1 + xi(p3+p4), p2 + p5 + xi p6)
        c1 = (xi(p9+p10) + p15, p11 + xi p12 + p16, p13 + p14 + p17)
    """
    (a0, a1, a2), (b0, b1, b2) = f

    p = T.fp2_mul_many(
        ctx,
        [
            (a0, l0), (a1, l0), (a2, l0),          # t0
            (b1, l2), (b2, l1), (b0, l1), (b2, l2), (b0, l2), (b1, l1),  # t1
            (a1, l2), (a2, l1), (a0, l1), (a2, l2), (a0, l2), (a1, l1),  # a*L1
            (b0, l0), (b1, l0), (b2, l0),          # b*L0
        ],
    )
    s78, s34, s910, s25, s1116, s1314 = T.fp2_add_many(
        ctx,
        [
            (p[7], p[8]),
            (p[3], p[4]),
            (p[9], p[10]),
            (p[2], p[5]),
            (p[11], p[16]),
            (p[13], p[14]),
        ],
    )
    x78, x34, x6, x910, x12 = T.fp2_mul_xi_many(
        ctx, [s78, s34, p[6], s910, p[12]]
    )
    c = T.fp2_add_many(
        ctx,
        [
            (p[0], x78),
            (p[1], x34),
            (s25, x6),
            (x910, p[15]),
            (s1116, x12),
            (s1314, p[17]),
        ],
    )
    return ((c[0], c[1], c[2]), (c[3], c[4], c[5]))


# ---------------------------------------------------------------------------
# Projective Miller-loop steps (spec: pairing_fast.py:120,149)
# ---------------------------------------------------------------------------


def _dbl_step(ctx, t, xp, yp):
    """Double T and return the tangent line at P=(xp, yp) (batched Fp).

    Three stacked levels (spec: pairing_fast.py:120 — identical algebra)."""
    sub = functools.partial(T.fp2_sub, ctx)
    small = functools.partial(T.fp2_small, ctx)

    x, y, z = t
    xx, y2, s, xy = T.fp2_batch(
        ctx, [("sqr", x), ("sqr", y), ("mul", y, z), ("mul", x, y)]
    )
    w = small(xx, 3)

    w2, bb, ss, sz, y2z, wx, wz = T.fp2_batch(
        ctx,
        [
            ("sqr", w),
            ("mul", xy, s),
            ("sqr", s),
            ("mul", s, z),
            ("mul", y2, z),
            ("mul", w, x),
            ("mul", w, z),
        ],
    )
    h = sub(w2, small(bb, 8))

    two_yp = limb.double_mod(ctx, yp)
    hs, wb, y2ss, sss, l0raw, l2 = T.fp2_batch(
        ctx,
        [
            ("mul", h, s),
            ("mul", w, sub(small(bb, 4), h)),
            ("mul", y2, ss),
            ("mul", s, ss),
            ("mul_fp", sz, two_yp),
            ("mul_fp", wz, limb.neg_mod(ctx, xp)),
        ],
    )
    x3 = T.fp2_double(ctx, hs)
    y3 = sub(wb, small(y2ss, 8))
    z3 = small(sss, 8)
    l0 = T.fp2_mul_xi(ctx, l0raw)
    l1 = sub(wx, T.fp2_double(ctx, y2z))
    return (x3, y3, z3), (l0, l1, l2)


def _add_step(ctx, t, q, xp, yp):
    """Mixed add T + affine Q; chord line at P=(xp, yp). Four stacked
    levels (spec: pairing_fast.py:149 — identical algebra)."""
    sub = functools.partial(T.fp2_sub, ctx)
    add = functools.partial(T.fp2_add, ctx)

    x, y, z = t
    x2, y2 = q
    y2z, x2z = T.fp2_mul_many(ctx, [(y2, z), (x2, z)])
    theta = sub(y, y2z)
    lam = sub(x, x2z)

    lam2, theta2, tx2, ly2, l0raw, l2 = T.fp2_batch(
        ctx,
        [
            ("sqr", lam),
            ("sqr", theta),
            ("mul", theta, x2),
            ("mul", lam, y2),
            ("mul_fp", lam, yp),
            ("mul_fp", theta, limb.neg_mod(ctx, xp)),
        ],
    )
    l0 = T.fp2_mul_xi(ctx, l0raw)
    l1 = sub(tx2, ly2)

    lam3, theta2z, lam2x = T.fp2_mul_many(
        ctx, [(lam2, lam), (theta2, z), (lam2, x)]
    )
    ww = add(sub(theta2z, T.fp2_double(ctx, lam2x)), lam3)

    x3, tt, lam3y, z3 = T.fp2_batch(
        ctx,
        [
            ("mul", lam, ww),
            ("mul", theta, sub(lam2x, ww)),
            ("mul", lam3, y),
            ("mul", lam3, z),
        ],
    )
    y3 = sub(tt, lam3y)
    return (x3, y3, z3), (l0, l1, l2)


def _neutral_line(ctx, batch_shape):
    return (
        T.fp2_one(ctx, batch_shape),
        T.fp2_zero(ctx, batch_shape),
        T.fp2_zero(ctx, batch_shape),
    )


def _mask_line(ctx, dead_mask, line, batch_shape):
    """Force identity-member pairs to contribute the neutral line l = 1."""
    neutral = _neutral_line(ctx, batch_shape)
    return tuple(
        T.fp2_select(dead_mask, n, l) for n, l in zip(neutral, line)
    )


def miller_loop(ctx: ModCtx, pairs):
    """Product of Miller loops over a static list of batched (p, q) pairs.

    p: affine G1 (x, y) Fp limb arrays; q: affine G2 (x, y) Fp2 elements.
    Affine (0, 0) lanes are identities and contribute 1.

    Multiple pairs are STACKED onto one extra leading axis and run as
    independent per-lane Miller loops, combined with a single fp12 mul at
    the end (valid since the final exponentiation distributes over the
    product). This keeps the scan body at ONE doubling step + ONE sparse
    multiply regardless of len(pairs) — the body op count, not the
    iteration count, is what dominates XLA compile time.
    """
    if len(pairs) > 1:
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(jnp.broadcast_arrays(*xs)), *pairs
        )
        lanes = miller_loop(ctx, [stacked])
        f = jax.tree_util.tree_map(lambda a: a[0], lanes)
        for i in range(1, len(pairs)):
            f = T.fp12_mul(
                ctx, f, jax.tree_util.tree_map(lambda a: a[i], lanes)
            )
        return f

    ((p, q),) = pairs
    batch_shape = p[0].shape[:-1]
    dead = jnp.logical_and(limb.is_zero(p[0]), limb.is_zero(p[1])) | (
        jnp.logical_and(T.fp2_is_zero(q[0]), T.fp2_is_zero(q[1]))
    )

    # constant scan-carry inits inherit the inputs' shard_map varying
    # axes (see limb.match_vary)
    vary = functools.partial(limb.match_vary, template=q[0][0])
    t0 = (
        q[0],
        q[1],
        jax.tree_util.tree_map(vary, T.fp2_one(ctx, batch_shape)),
    )
    f0 = jax.tree_util.tree_map(vary, T.fp12_one(ctx, batch_shape))
    bits = jnp.asarray(X_BITS)

    def dbl(carry):
        f, t = carry
        t2, line = _dbl_step(ctx, t, p[0], p[1])
        line = _mask_line(ctx, dead, line, batch_shape)
        return fp12_mul_sparse_line(ctx, f, *line), t2

    def add(carry):
        f, t = carry
        t2, line = _add_step(ctx, t, q, p[0], p[1])
        line = _mask_line(ctx, dead, line, batch_shape)
        return fp12_mul_sparse_line(ctx, f, *line), t2

    def step(carry, bit):
        carry = dbl((T.fp12_sqr(ctx, carry[0]), carry[1]))
        carry = lax.cond(bit != 0, add, lambda c: c, carry)
        return carry, None

    # First schedule entry skips the squaring (f == 1 — squaring is a no-op,
    # so we just run the uniform step).
    (f, _), _ = lax.scan(step, (f0, t0), bits)
    if X_IS_NEG:
        f = T.fp12_conj(ctx, f)
    return f


# ---------------------------------------------------------------------------
# Final exponentiation (spec: pairing_fast.py:211-244)
# ---------------------------------------------------------------------------


def _cyc_pow_u(ctx, f):
    """f^|x| in the cyclotomic subgroup: scan over the bits of |x| with
    Granger–Scott squarings and a selected multiply (6 of 64 bits set)."""
    bits = jnp.asarray(X_BITS_FULL[1:])  # leading 1: start from f

    def step(acc, bit):
        acc = T.fp12_cyclotomic_sqr(ctx, acc)
        mul = T.fp12_mul(ctx, acc, f)
        return jax.tree_util.tree_map(
            lambda m, a: jnp.where(bit != 0, m, a), mul, acc
        ), None

    acc, _ = lax.scan(step, f, bits)
    return acc


def _cyc_pow_x(ctx, f):
    out = _cyc_pow_u(ctx, f)
    return T.fp12_conj(ctx, out) if X_IS_NEG else out


def final_exp(ctx: ModCtx, f):
    """f^(3 * (p^12-1)/r): easy part, then the lattice-identity hard part."""
    # Easy part: f^((p^6-1)(p^2+1)) — lands in the cyclotomic subgroup.
    f = T.fp12_mul(ctx, T.fp12_conj(ctx, f), T.fp12_inv(ctx, f))
    m = T.fp12_mul(ctx, T.fp12_frobenius_n(ctx, f, 2), f)
    # Hard part: m^(3h) = m^((x-1)^2 (x+p) (x^2+p^2-1)) * m^3.
    a = T.fp12_mul(ctx, _cyc_pow_u(ctx, m), m)  # m^(u+1)
    a = T.fp12_mul(ctx, _cyc_pow_u(ctx, a), a)  # m^((x-1)^2)
    b = T.fp12_mul(ctx, _cyc_pow_x(ctx, a), T.fp12_frobenius(ctx, a))
    c = T.fp12_mul(
        ctx,
        T.fp12_mul(
            ctx,
            _cyc_pow_x(ctx, _cyc_pow_x(ctx, b)),
            T.fp12_frobenius_n(ctx, b, 2),
        ),
        T.fp12_conj(ctx, b),
    )
    m3 = T.fp12_mul(ctx, T.fp12_cyclotomic_sqr(ctx, m), m)
    return T.fp12_mul(ctx, c, m3)


def multi_pairing_check(ctx: ModCtx, pairs):
    """Batch mask: prod e(p_i, q_i) == 1 (computed as the cube — sound:
    GT has prime order r and gcd(3, r) = 1)."""
    f = miller_loop(ctx, pairs)
    e = final_exp(ctx, f)
    return T.fp12_is_one(ctx, e)


# ---------------------------------------------------------------------------
# BLS verification kernels (eth2 flavour: pubkeys G1, signatures/messages G2)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _neg_g1_gen_consts(ctx: ModCtx):
    x, y = REF.g1_neg(REF.G1_GEN)
    return (
        np.asarray(limb.pack_mont_host(ctx, [x])[0]),
        np.asarray(limb.pack_mont_host(ctx, [y])[0]),
    )


def neg_g1_gen(ctx: ModCtx, batch_shape=()):
    """-G1 generator broadcast to a batch shape (the fixed verify pair)."""
    x, y = _neg_g1_gen_consts(ctx)
    return (
        jnp.broadcast_to(jnp.asarray(x), (*batch_shape, ctx.n_limbs)),
        jnp.broadcast_to(jnp.asarray(y), (*batch_shape, ctx.n_limbs)),
    )


def batched_verify(ctx: ModCtx, pk, msg, sig):
    """Per-lane BLS verify: e(pk, H(m)) == e(G1, sig), i.e.
    e(pk, H(m)) * e(-G1, sig) == 1.

    pk: batched affine G1; msg: batched affine G2 (already hashed to the
    curve); sig: batched affine G2. Returns a bool mask over the batch.
    """
    batch_shape = pk[0].shape[:-1]
    return multi_pairing_check(
        ctx,
        [(pk, msg), (neg_g1_gen(ctx, batch_shape), sig)],
    )


def _fp12_prod_tree(ctx: ModCtx, f):
    """Product of a [N, ...] batch of Fp12 values over the leading axis in
    log2(N) stacked multiplies (N static; padded to a power of two with
    ones)."""

    n = jax.tree_util.tree_leaves(f)[0].shape[0]
    pow2 = 1 << (n - 1).bit_length()
    if pow2 != n:
        rest = jax.tree_util.tree_leaves(f)[0].shape[1:-1]
        ones = T.fp12_one(ctx, (pow2 - n, *rest))
        # inherit shard_map varying axes from a length-1 slice (the pad
        # block's leading dim differs from the source's)
        ones = jax.tree_util.tree_map(
            lambda o, ref: o + ref[:1] * jnp.zeros((), ref.dtype), ones, f
        )
        f = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate((a, b), axis=0), f, ones
        )
        n = pow2
    while n > 1:
        half = n // 2
        a = jax.tree_util.tree_map(lambda x: x[:half], f)
        b = jax.tree_util.tree_map(lambda x: x[half:], f)
        f = T.fp12_mul(ctx, a, b)
        n = half
    return jax.tree_util.tree_map(lambda x: x[0], f)


def _pad_pow2(C, f, pts, axis: int, n: int):
    """Pad a (possibly batched) point axis to the next power of two with
    identity points that inherit the source's shard_map varying axes."""
    pow2 = 1 << (n - 1).bit_length()
    if pow2 == n:
        return pts, n
    lead = jax.tree_util.tree_leaves(pts)[0].shape[:axis]
    ident = C.point_identity(f, (*lead, pow2 - n))

    def vary(o, ref):
        slicer = [slice(None)] * axis + [slice(0, 1)]
        return o + ref[tuple(slicer)] * jnp.zeros((), ref.dtype)

    ident = jax.tree_util.tree_map(vary, ident, pts)
    pts = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate((a, b), axis=axis), pts, ident
    )
    return pts, pow2


def _point_sum_tree(C, f, pts, n: int, axis: int = 0):
    """Log-depth pairwise sum of projective points over `axis` (any
    static size — padded to a power of two with identities; complete
    adds are identity-safe)."""
    pts, n = _pad_pow2(C, f, pts, axis, n)
    sl = lambda x, a, b: x[
        tuple([slice(None)] * axis + [slice(a, b)])
    ]
    while n > 1:
        half = n // 2
        a = jax.tree_util.tree_map(lambda x: sl(x, 0, half), pts)
        b = jax.tree_util.tree_map(lambda x: sl(x, half, None), pts)
        pts = C.point_add(f, a, b)
        n = half
    return jax.tree_util.tree_map(
        lambda x: x[tuple([slice(None)] * axis + [0])], pts
    )


def _point_sum_scan(C, f, pts, n: int, rows: int):
    """Sum of projective points over axis 0 — [n, *grid] in, [*grid] out
    — as ONE lax.scan of complete adds on flat lanes. The points are cut
    into slices of `rows` flat lanes (a power of two; a whole number of
    axis-0 entries, at least one and at most all n); the scan adds the
    slices into the first, one a step, then folds what it holds by
    log2 steps of acc + roll(acc) at halving distances, after which
    every entry of the slice is the total, and the first is returned.
    The compiled module holds the add ONCE, in the scan's body, where a
    halving tree over n holds it log2(n) times: an unrolled level of a
    complete G2 add is ~17 MB of generated code on a v5e, and a plane
    program's load at boot goes with its code. A step of adds costs the
    device the same from 32 lanes to a kernel tile of them (launches:
    PERF.md §6 PR 42), so slices of one tile make the fewest steps."""
    pts, n = _pad_pow2(C, f, pts, 0, n)
    grid = jax.tree_util.tree_leaves(pts)[0].shape[1:-1]
    width = int(np.prod(grid, dtype=np.int64))  # flat lanes an entry
    per = max(1, min(rows // width, n))  # entries a slice
    slices = n // per
    parts = jax.tree_util.tree_map(
        lambda a: a.reshape(slices, per * width, a.shape[-1]), pts
    )

    def step(acc, k):
        nxt = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(
                a, jnp.minimum(k + 1, slices - 1), 0, keepdims=False
            ),
            parts,
        )
        # once the slices are in: per / 2, per / 4 .. 1 entries away
        away = (per * width) >> jnp.maximum(k + 2 - slices, 1)
        other = jax.tree_util.tree_map(
            lambda a, b: jnp.where(
                k + 1 < slices, a, jnp.roll(b, -away, axis=0)
            ),
            nxt,
            acc,
        )
        return C.point_add(f, acc, other), None

    acc, _ = lax.scan(
        step,
        jax.tree_util.tree_map(lambda a: a[0], parts),
        jnp.arange(slices - 1 + per.bit_length() - 1),
    )
    return jax.tree_util.tree_map(
        lambda a: a[:width].reshape(*grid, a.shape[-1]), acc
    )


def batched_verify_grouped_rlc(
    ctx: ModCtx, fr_ctx: ModCtx, pk, msg, sig, rand, nbits: int = 64
):
    """Grouped random-linear-combination batch verification:

        prod_m e( sum_{i in m} r_i * pk_i,  H(m) )  *  e(-G1, sum_i r_i * sig_i) == 1

    Layout: lanes pre-grouped by message on host — pk/sig/rand have shape
    [M, K] (M distinct messages, K lanes per group, padded with identity
    points + ZERO exponents), msg has shape [M].

    Per lane the pairing work collapses to one 64-bit G1 double-and-add
    and one 64-bit G2 double-and-add; the Miller stage runs over only
    M + 1 pairs and ONE final exponentiation — at production scale
    (thousands of partial signatures over a handful of duty roots per
    slot: every validator in a committee signs the same attestation
    data) this is ~10x fewer field ops per signature than the per-lane
    kernel, and the compiled program's Miller batch no longer grows with
    the signature count. Same 2^-nbits Schwartz-Zippel soundness as
    batched_verify_rlc (per-lane independent exponents bind each pk/sig
    pair); the construction consensus clients use for gossip batches.

    Returns a scalar bool (all-valid).
    """
    from charon_tpu.ops import curve as C

    g1f, g2f = C.g1_ops(ctx), C.g2_ops(ctx)
    m_groups, k = pk[0].shape[0], pk[0].shape[1]

    def flat2(t):
        return jax.tree_util.tree_map(
            lambda a: a.reshape(m_groups * k, *a.shape[2:]), t
        )

    rand_flat = rand.reshape(m_groups * k, -1)
    pk_proj = C.affine_to_point(g1f, flat2(pk))
    sig_proj = C.affine_to_point(g2f, flat2(sig))

    from charon_tpu.ops import msm as MSM

    if MSM.msm_active():
        # Pippenger bucket MSM shares the randomization work across
        # lanes: per-message G1 bucket sums in one segmented reduction,
        # the G2 aggregate as the single-segment case (~8x fewer
        # point-ops than per-lane double-and-add at nbits=64, w=8)
        seg = jnp.repeat(jnp.arange(m_groups, dtype=jnp.int32), k)
        buckets = MSM.msm_segmented(
            g1f, fr_ctx, pk_proj, rand_flat, seg, m_groups, nbits=nbits
        )
        s_total = MSM.msm(g2f, fr_ctx, sig_proj, rand_flat, nbits=nbits)
    else:
        # per-lane 64-bit scalar muls (zero exponents -> identity)
        pk_r = C.point_scalar_mul(g1f, fr_ctx, pk_proj, rand_flat, nbits=nbits)
        sig_r = C.point_scalar_mul(
            g2f, fr_ctx, sig_proj, rand_flat, nbits=nbits
        )

        # per-group sums over the K axis -> [M], then the G2 total over M
        def regroup(t, f):
            t = jax.tree_util.tree_map(
                lambda a: a.reshape(m_groups, k, *a.shape[1:]), t
            )
            return _point_sum_tree(C, f, t, k, axis=1)

        buckets = regroup(pk_r, g1f)  # [M] G1 projective
        sig_groups = regroup(sig_r, g2f)  # [M] G2 projective
        s_total = _point_sum_tree(C, g2f, sig_groups, m_groups)

    return grouped_rlc_check(ctx, buckets, msg, s_total)


def grouped_rlc_check(ctx: ModCtx, buckets, msgs, s_total):
    """The grouped-RLC verification equation's shared tail: per-group
    bucket pairs e(B_m, H_m) ++ ONE aggregate pair e(-G1, S), a single
    product tree and ONE final exponentiation; True iff the product is
    1. `buckets`: [M] projective G1 bucket sums; `msgs`: [M] affine G2
    message points; `s_total`: projective G2 aggregate. Soundness-
    critical — both batched_verify_grouped_rlc and the sharded mesh
    plane (parallel/mesh.py) verify through THIS function."""
    g1f, g2f = C.g1_ops(ctx), C.g2_ops(ctx)
    bucket_aff = C.point_to_affine(g1f, buckets)
    s_aff = C.point_to_affine(g2f, s_total)

    def append_lane(a, b):
        return jnp.concatenate((a, b[None, ...]), axis=0)

    neg_g = neg_g1_gen(ctx, ())
    pk_lanes = jax.tree_util.tree_map(append_lane, bucket_aff, neg_g)
    q_lanes = jax.tree_util.tree_map(append_lane, msgs, s_aff)
    f_lanes = miller_loop(ctx, [(pk_lanes, q_lanes)])  # [M+1] fp12
    f_tot = _fp12_prod_tree(ctx, f_lanes)
    e = final_exp(ctx, f_tot)
    return T.fp12_is_one(ctx, e)


def point_sum_tree(f, pts, n: int, axis: int = 0):
    """Public log-depth point reduction (pairwise complete adds)."""
    return _point_sum_tree(C, f, pts, n, axis=axis)


def batched_verify_rlc(
    ctx: ModCtx, fr_ctx: ModCtx, pk, msg, sig, rand, nbits: int = 64
):
    """Whole-batch BLS verification by random linear combination in GT:

        prod_i (e(pk_i, H(m_i)) * e(-G1, sig_i))^(r_i) == 1
      = prod_i e(pk_i^(r_i), H(m_i)) * e((-G1)^(r_i), sig_i) == 1

    with caller-supplied random nonzero `nbits`-bit exponents r_i (raw
    Fr limb array, shape [N, fr_limbs]). Lane i's verification value
    v_i = e(pk_i, H_i) * e(-G1, sig_i) is 1 iff the lane is valid, so a
    batch with any forged lane passes only with probability 2^-nbits
    over the verifier's randomness (Schwartz-Zippel in the exponent) —
    the standard batch-verification trick consensus clients use for
    gossip attestation batches. On False, re-run the per-lane
    `batched_verify` to attribute.

    Cost per lane vs batched_verify: the per-lane final exponentiation
    (the most expensive per-lane stage) is replaced by one stacked
    64-bit G1 double-and-add and a log2(N)-depth fp12 product tree with
    ONE shared final exponentiation. The Miller stage is byte-identical
    in structure (same stacked 2-pair scan), so the compiled program is
    no bigger than the per-lane kernel's. The recombine programs' form:
    their 32- and 64-row batches sit under one kernel tile with two
    pairs a lane or one; batched_verify_rlc_sets (the parsed verify
    program) sums r_i * sig_i in G2 and pairs the sum once a set.

    Returns a scalar bool (all-valid).
    """
    from charon_tpu.ops import curve as C

    g1f = C.g1_ops(ctx)

    # One stacked 64-bit scalar mul covers both G1 sides: [2, N] points
    # (pk_i and broadcast -G1), same exponent r_i for both rows.
    batch_shape = pk[0].shape[:-1]
    neg_g = neg_g1_gen(ctx, batch_shape)
    pts = jax.tree_util.tree_map(
        lambda a, b: jnp.stack(jnp.broadcast_arrays(a, b)), pk, neg_g
    )
    rand2 = jnp.stack(jnp.broadcast_arrays(rand, rand))
    scaled = C.point_scalar_mul(
        g1f, fr_ctx, C.affine_to_point(g1f, pts), rand2, nbits=nbits
    )
    aff = C.point_to_affine(g1f, scaled)
    pk_r = jax.tree_util.tree_map(lambda a: a[0], aff)
    negg_r = jax.tree_util.tree_map(lambda a: a[1], aff)

    f_lanes = miller_loop(ctx, [(pk_r, msg), (negg_r, sig)])  # [N] fp12
    f_tot = _fp12_prod_tree(ctx, f_lanes)
    e = final_exp(ctx, f_tot)
    return T.fp12_is_one(ctx, e)


def batched_verify_rlc_sets(
    ctx: ModCtx, fr_ctx: ModCtx, pk, msg, sig, rand, seg, n_sets: int,
    nbits: int = 64,
):
    """RLC verification with one verdict per SET, the signature side
    summed in G2: `seg` is an int32 [N] segment id per lane (0 <= seg <
    n_sets, n_sets static) and the answer a bool [n_sets] — set s passes
    iff

        prod_{i : seg_i == s} e(r_i * pk_i, H(m_i))  *  e(-G1, S_s) == 1,
        S_s = sum_{i : seg_i == s} r_i * sig_i

    which is batched_verify_rlc's product over the set's lanes, factor
    for factor (e(r_i * (-G1), sig_i) = e(-G1, r_i * sig_i), and the
    pairing is bilinear on the sum because decompression checked every
    sig_i into the r-torsion subgroup). Per lane: one 64-bit G1
    double-and-add (r_i * pk_i), one 64-bit G2 double-and-add (r_i *
    sig_i), ONE Miller pair; per set: the sum S_s (_point_sum_scan over
    the identity-masked [N, n_sets] grid: one scan of complete G2 adds,
    a kernel tile of lanes a step), one Miller pair (-G1, S_s) riding
    the same Miller scan as lanes N .. N + n_sets - 1, a masked Fp12
    product tree; one inversion over N + n_sets rows takes the lanes
    and the sums to affine together, and ONE final exponentiation over
    the [n_sets] batch ends it. Each set's
    product is its own Schwartz-Zippel check at 2^-nbits under the lanes'
    own independent exponents (two genuine signatures swapped between two
    lanes of a set fail it), and the whole-batch answer is the AND of the
    sets'. An empty segment sums to the identity — affine (0, 0), a dead
    Miller pair — its product is 1 and reads True; a lane with exponent 0
    (padding, undecodable) is the identity on both sides, neutral in
    whichever segment it rides. A partial-signature set is dropped whole
    on one bad lane, so a verdict per set is the verdict the protocol
    needs — on a failing batch this saves the per-lane program's
    dispatch.
    """
    from charon_tpu.ops.pallas_mont import TILE

    g1f, g2f = C.g1_ops(ctx), C.g2_ops(ctx)
    n = seg.shape[0]
    pk_r = C.point_scalar_mul(
        g1f, fr_ctx, C.affine_to_point(g1f, pk), rand, nbits=nbits
    )
    sig_r = C.point_scalar_mul(
        g2f, fr_ctx, C.affine_to_point(g2f, sig), rand, nbits=nbits
    )
    in_set = seg[:, None] == jnp.arange(n_sets, dtype=seg.dtype)[None, :]

    def by_set(lanes, neutral):
        # [N, n_sets]: lane i's value in its own segment's column, the
        # neutral element in the others; a fold over axis 0 then takes
        # every set's sum (product) at once
        return jax.tree_util.tree_map(
            lambda a, o: jnp.where(in_set[..., None], a[:, None, :], o),
            lanes,
            neutral,
        )

    def append(a, b):
        return jnp.concatenate((a, b), axis=0)

    s_sets = _point_sum_scan(
        C, g2f, by_set(sig_r, C.point_identity(g2f, (n_sets,))), n, TILE
    )
    # both conversions to affine (identity -> (0, 0)) through ONE Fermat
    # inversion: its 381-step chain costs a kernel launch a step whatever
    # the rows, so the norms of the n_sets sums' Z (Fp2: 1 / z = conj(z) /
    # norm(z)) ride with the lanes' Z
    z0, z1 = s_sets[2]
    norm = limb.add_mod(ctx, limb.mont_sqr(ctx, z0), limb.mont_sqr(ctx, z1))
    inv = limb.inv_mod(ctx, append(pk_r[2], norm))
    zinv_s = (
        limb.mont_mul(ctx, z0, inv[n:]),
        limb.neg_mod(ctx, limb.mont_mul(ctx, z1, inv[n:])),
    )
    pk_aff = tuple(g1f.mul(c, inv[:n]) for c in pk_r[:2])
    s_aff = tuple(g2f.mul(c, zinv_s) for c in s_sets[:2])  # [n_sets]

    p_lanes = jax.tree_util.tree_map(
        append, pk_aff, neg_g1_gen(ctx, (n_sets,))
    )
    q_lanes = jax.tree_util.tree_map(append, msg, s_aff)
    f_lanes = miller_loop(ctx, [(p_lanes, q_lanes)])  # [N + n_sets] fp12
    f_sets = _fp12_prod_tree(
        ctx,
        by_set(
            jax.tree_util.tree_map(lambda a: a[:n], f_lanes),
            T.fp12_one(ctx, (n_sets,)),
        ),
    )
    # aggregate lane N + s is set s's alone: one multiply, no mask
    f_sets = T.fp12_mul(
        ctx, f_sets, jax.tree_util.tree_map(lambda a: a[n:], f_lanes)
    )
    return T.fp12_is_one(ctx, final_exp(ctx, f_sets))  # [n_sets]
