"""Batched G1/G2 point arithmetic with complete projective formulas.

TPU-first design choice: instead of the reference's branchy affine formulas
(it calls herumi one point at a time — ref: tbls/herumi.go:225-247
Aggregate), we use the *complete* homogeneous-projective addition and
doubling formulas of Renes–Costello–Batina 2015 (eprint 2015/1060,
algorithms 7 and 9 for a = 0). Complete formulas are branch-free: they are
correct for identity inputs, equal inputs, and inverses, so the whole batch
flows through identical straight-line code — exactly what XLA wants.

Points are (X, Y, Z) tuples of field elements; the identity is (0, 1, 0).
G1 uses Fp limbs directly, G2 uses fptower Fp2 pairs. Both share the same
code via a tiny field-ops vtable.

Curve constants: E1: y^2 = x^3 + 4, E2: y^2 = x^3 + 4(1+u), so
b3 = 12 for G1 and 12*(1+u) = 12*xi for G2.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax.numpy as jnp
from jax import lax

from charon_tpu.crypto import g1g2 as REF
from charon_tpu.crypto.fields import P, X_ABS
from charon_tpu.ops import fptower as T
from charon_tpu.ops import limb
from charon_tpu.ops.limb import ModCtx


@dataclasses.dataclass(frozen=True)
class FieldOps:
    """Vtable making point formulas generic over Fp (G1) and Fp2 (G2)."""

    name: str
    ctx: ModCtx
    add: Callable
    sub: Callable
    mul: Callable
    sqr: Callable
    double: Callable
    neg: Callable
    small: Callable  # (a, k: static int) -> k*a
    mul_b3: Callable  # multiply by 3*b
    inv: Callable
    is_zero: Callable
    select: Callable
    zero: Callable  # (batch_shape) -> 0
    one: Callable  # (batch_shape) -> 1
    batch_shape: Callable  # element -> batch shape tuple
    batch: Callable  # (ops list of ("mul",a,b)/("sqr",a)) -> results; one
    # stacked base mul per dependency level (see fptower.fp2_batch)


@functools.lru_cache(maxsize=None)
def g1_ops(ctx: ModCtx) -> FieldOps:
    return FieldOps(
        name="g1",
        ctx=ctx,
        add=functools.partial(limb.add_mod, ctx),
        sub=functools.partial(limb.sub_mod, ctx),
        mul=functools.partial(limb.mont_mul, ctx),
        sqr=functools.partial(limb.mont_sqr, ctx),
        double=functools.partial(limb.double_mod, ctx),
        neg=functools.partial(limb.neg_mod, ctx),
        small=lambda a, k: _small_fp(ctx, a, k),
        mul_b3=lambda a: _small_fp(ctx, a, 12),
        inv=functools.partial(limb.inv_mod, ctx),
        is_zero=limb.is_zero,
        select=limb.select,
        zero=lambda shape=(): limb.zeros(ctx, shape),
        one=lambda shape=(): limb.const(ctx, 1, shape),
        batch_shape=lambda a: a.shape[:-1],
        batch=functools.partial(_fp_batch, ctx),
    )


@functools.lru_cache(maxsize=None)
def g2_ops(ctx: ModCtx) -> FieldOps:
    return FieldOps(
        name="g2",
        ctx=ctx,
        add=functools.partial(T.fp2_add, ctx),
        sub=functools.partial(T.fp2_sub, ctx),
        mul=functools.partial(T.fp2_mul, ctx),
        sqr=functools.partial(T.fp2_sqr, ctx),
        double=functools.partial(T.fp2_double, ctx),
        neg=functools.partial(T.fp2_neg, ctx),
        small=functools.partial(T.fp2_small, ctx),
        mul_b3=lambda a: T.fp2_small(ctx, T.fp2_mul_xi(ctx, a), 12),
        inv=functools.partial(T.fp2_inv, ctx),
        is_zero=T.fp2_is_zero,
        select=T.fp2_select,
        zero=lambda shape=(): T.fp2_zero(ctx, shape),
        one=lambda shape=(): T.fp2_one(ctx, shape),
        batch_shape=lambda a: a[0].shape[:-1],
        batch=functools.partial(T.fp2_batch, ctx),
    )


def _fp_batch(ctx, ops):
    """Stacked base muls for the Fp (G1) field — mirrors fptower.fp2_batch."""
    xs, ys = [], []
    for op in ops:
        if op[0] == "mul":
            xs.append(op[1])
            ys.append(op[2])
        elif op[0] == "sqr":
            xs.append(op[1])
            ys.append(op[1])
        else:
            raise ValueError(op[0])
    prods = limb.mont_mul(ctx, jnp.stack(xs), jnp.stack(ys))
    return [prods[i] for i in range(len(ops))]


def _small_fp(ctx, a, k: int):
    if k == 0:
        return limb.zeros(ctx, a.shape[:-1])
    acc = None
    add = a
    while k:
        if k & 1:
            acc = add if acc is None else limb.add_mod(ctx, acc, add)
        k >>= 1
        if k:
            add = limb.double_mod(ctx, add)
    return acc


# ---------------------------------------------------------------------------
# Complete projective add / double (RCB15 algorithms 7 and 9, a = 0)
# ---------------------------------------------------------------------------


def point_identity(f: FieldOps, batch_shape=()):
    return (f.zero(batch_shape), f.one(batch_shape), f.zero(batch_shape))


def point_add(f: FieldOps, p, q):
    """Complete addition, RCB15 algorithm 7 (a=0). 12 field muls in two
    stacked levels."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    t0, t1, t2, a, b, c = f.batch(
        [
            ("mul", x1, x2),
            ("mul", y1, y2),
            ("mul", z1, z2),
            ("mul", f.add(x1, y1), f.add(x2, y2)),
            ("mul", f.add(y1, z1), f.add(y2, z2)),
            ("mul", f.add(x1, z1), f.add(x2, z2)),
        ]
    )
    t3 = f.sub(a, f.add(t0, t1))  # x1y2 + x2y1
    t4 = f.sub(b, f.add(t1, t2))  # y1z2 + y2z1
    y3 = f.sub(c, f.add(t0, t2))  # x1z2 + x2z1
    t0 = f.small(t0, 3)  # 3 x1x2
    t2 = f.mul_b3(t2)  # b3 z1z2
    z3 = f.add(t1, t2)
    t1 = f.sub(t1, t2)
    y3 = f.mul_b3(y3)  # b3 (x1z2 + x2z1)
    m1, m2, m3, m4, m5, m6 = f.batch(
        [
            ("mul", t3, t1),
            ("mul", t4, y3),
            ("mul", y3, t0),
            ("mul", t1, z3),
            ("mul", z3, t4),
            ("mul", t0, t3),
        ]
    )
    return (f.sub(m1, m2), f.add(m3, m4), f.add(m5, m6))


def point_double(f: FieldOps, p):
    """Complete doubling, RCB15 algorithm 9 (a=0). 6 muls + 2 squarings in
    two stacked levels."""
    x, y, z = p
    t0, t1, zz, xy = f.batch(
        [("sqr", y), ("mul", y, z), ("sqr", z), ("mul", x, y)]
    )
    z3c = f.small(t0, 8)
    t2 = f.mul_b3(zz)
    y3 = f.add(t0, t2)
    t0 = f.sub(t0, f.small(t2, 3))
    x3, z3, ty, xyt = f.batch(
        [
            ("mul", t2, z3c),
            ("mul", t1, z3c),
            ("mul", t0, y3),
            ("mul", xy, t0),
        ]
    )
    return (f.double(xyt), f.add(ty, x3), z3)


def point_neg(f: FieldOps, p):
    return (p[0], f.neg(p[1]), p[2])


def point_select(f: FieldOps, mask, p, q):
    return tuple(f.select(mask, a, b) for a, b in zip(p, q))


def point_is_identity(f: FieldOps, p):
    return f.is_zero(p[2])


def point_to_affine(f: FieldOps, p):
    """(X, Y, Z) -> (x, y) with the identity mapping to (0, 0).

    Batched Fermat inversion; Z = 0 lanes produce 0 (inv_mod(0) == 0)."""
    zinv = f.inv(p[2])
    return (f.mul(p[0], zinv), f.mul(p[1], zinv))


def affine_to_point(f: FieldOps, a):
    """(x, y) affine -> projective; (0, 0) is interpreted as the identity
    (safe: y = 0 never occurs on these curves since b != 0)."""
    x, y = a
    is_id = jnp.logical_and(f.is_zero(x), f.is_zero(y))
    shape = f.batch_shape(x)
    one = f.one(shape)
    zero = f.zero(shape)
    return (
        x,
        f.select(is_id, one, y),
        f.select(is_id, zero, one),
    )


# ---------------------------------------------------------------------------
# Batched scalar multiplication (dynamic per-element scalars)
# ---------------------------------------------------------------------------


def _scalar_bits_msb(fr_ctx: ModCtx, scalars, nbits: int):
    """Raw (non-Montgomery) Fr limb array (..., n_limbs) -> (nbits, ...)
    bit array, MSB first, as the scan schedule."""
    shifts = jnp.arange(fr_ctx.limb_bits, dtype=scalars.dtype)
    bits = (scalars[..., None] >> shifts) & fr_ctx.u(1)  # (..., n_limbs, lb)
    bits = bits.reshape(*scalars.shape[:-1], -1)[..., :nbits]  # little-endian
    bits = jnp.flip(bits, axis=-1)  # MSB first
    return jnp.moveaxis(bits, -1, 0)


def point_scalar_mul(f: FieldOps, fr_ctx: ModCtx, p, scalars, nbits: int = 255):
    """[k]P for batched projective points and per-element raw Fr scalars.

    Left-to-right double-and-add as a lax.scan over the bit schedule with a
    branch-free select — uniform work per step, fully vectorized over the
    batch. ~nbits * (1 dbl + 1 add) field ops.
    """
    bits = _scalar_bits_msb(fr_ctx, scalars, nbits)
    import jax

    template = p[0][0] if isinstance(p[0], tuple) else p[0]
    identity = jax.tree_util.tree_map(
        lambda a: limb.match_vary(a, template),
        point_identity(f, f.batch_shape(p[0])),
    )

    def step(acc, bit):
        acc = point_double(f, acc)
        added = point_add(f, acc, p)
        return point_select(f, bit != 0, added, acc), None

    acc, _ = lax.scan(step, identity, bits)
    return acc


def point_sum(f: FieldOps, p, axis: int = -1):
    """Reduce-add points over a (small, static) batch axis.

    Points are (X, Y, Z) field pytrees; `axis` indexes a batch axis of the
    underlying limb arrays (negative axes count from the last batch axis).
    Implemented as a sequential fold of complete adds — callers use this for
    the threshold axis (t <= ~7)."""

    def leaf_slices(leaf):
        # normalize axis to the batch axes (last dim is limbs)
        ax = axis if axis >= 0 else leaf.ndim - 1 + axis
        return [
            jnp.take(leaf, i, axis=ax) for i in range(leaf.shape[ax])
        ]

    import jax

    sliced = jax.tree_util.tree_map(leaf_slices, p)
    leaves, treedef = jax.tree_util.tree_flatten(sliced, is_leaf=lambda x: isinstance(x, list))
    n = len(leaves[0])
    terms = [
        jax.tree_util.tree_unflatten(treedef, [l[i] for l in leaves])
        for i in range(n)
    ]
    acc = terms[0]
    for t in terms[1:]:
        acc = point_add(f, acc, t)
    return acc


# ---------------------------------------------------------------------------
# Four-base G2 multiplication over the psi endomorphism
#
# On G2 psi acts as multiplication by the BLS parameter x = -X_ABS
# (ops/decompress.py: the subgroup check is exactly psi(P) == [x]P), so
#
#     [k]P = [d0]P + [d1](-psi P) + [d2](psi^2 P) + [d3](-psi^3 P)
#
# with d0..d3 the base-X_ABS digits of k: k < r < X_ABS^4, so four digits,
# each under X_ABS < 2^64. One joint double-and-add of 64 steps over a
# 16-entry table of the bases' subset sums does the work of 255 steps over
# one base. TRUE ONLY for P of order r: blsops.threshold_recombine, the one
# caller, says where each of ITS callers' points were subgroup-checked.
# ---------------------------------------------------------------------------

PSI_STEPS = X_ABS.bit_length()  # 64: a digit's bits, the scan's steps
_CUT_BITS = 255  # a cut scalar is under 2^255 (Fr elements are: r < 2^255)


@functools.lru_cache(maxsize=None)
def _psi_cut_consts(fr_ctx: ModCtx):
    """(shift, reciprocal limbs) for X_ABS, X_ABS^2, X_ABS^3, and X_ABS's
    limbs. With s = 255 + bitlen(d) and m = floor(2^s / d) + 1,
    floor(k * m / 2^s) == floor(k / d) for EVERY k < 2^255: m * d - 2^s
    is some e in (0, d], and k * e < 2^255 * 2^bitlen(d) = 2^s keeps the
    estimate's excess under 1 / d (Granlund-Montgomery 1994, thm 4.2).
    No correction step."""
    shifts, recips = [], []
    for i in (1, 2, 3):
        d = X_ABS**i
        s = _CUT_BITS + d.bit_length()
        m = (1 << s) // d + 1
        if m.bit_length() > fr_ctx.n_limbs * fr_ctx.limb_bits:
            raise ValueError("reciprocal wider than the Fr limbs")
        shifts.append(s)
        recips.append(
            limb.int_to_limbs(m, fr_ctx.n_limbs, fr_ctx.limb_bits, fr_ctx.np_dtype)
        )
    x = limb.int_to_limbs(X_ABS, fr_ctx.n_limbs, fr_ctx.limb_bits, fr_ctx.np_dtype)
    return tuple(shifts), tuple(recips), x


def _limbs_shift_right(ctx: ModCtx, t, s: int):
    """floor(t / 2^s) as ctx.n_limbs limbs, t canonical limbs (..., w)."""
    q, r = divmod(s, ctx.limb_bits)
    n = ctx.n_limbs

    def window(start):
        w = t[..., start : start + n]
        short = n - w.shape[-1]
        return jnp.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, short)]) if short else w

    if r == 0:
        return window(q)
    return (window(q) >> r) | ((window(q + 1) << (ctx.limb_bits - r)) & ctx.u(ctx.mask))


def psi_digits(fr_ctx: ModCtx, scalars):
    """Raw Fr limbs (..., n_limbs), each scalar under 2^255 -> its four
    base-X_ABS digits (4, ..., n_limbs), least significant first:
    sum_i d_i * X_ABS^i == k exactly, every d_i < X_ABS. Integer limb
    work: three exact quotients by reciprocal (one stacked product), three
    remainders (one stacked product and subtraction)."""
    shifts, recips, x = _psi_cut_consts(fr_ctx)
    recips = jnp.stack(recips).reshape(3, *([1] * (scalars.ndim - 1)), -1)
    prod, _ = limb._normalize(fr_ctx, limb._conv_full(fr_ctx, scalars[None], recips))
    # q[i] = floor(k / X_ABS^(i+1))
    q = [_limbs_shift_right(fr_ctx, prod[i], s) for i, s in enumerate(shifts)]
    # d_i = floor(k / X^i) - floor(k / X^(i+1)) * X lies in [0, X): taken
    # mod 2^(limb bits) it is exact
    back, _ = limb._normalize(
        fr_ctx, limb._conv_low(fr_ctx, jnp.stack(q), jnp.asarray(x))
    )
    low = jnp.stack([scalars, q[0], q[1]]) + (fr_ctx.u(fr_ctx.mask) - back)
    low, _ = limb._normalize(fr_ctx, low + jnp.asarray(limb._one0(fr_ctx)), passes=1)
    return jnp.concatenate([low, q[2][None]])


def g2_psi_bases(ctx: ModCtx, affine):
    """Affine G2 lanes P [L] -> the affine bases (P, -psi P, psi^2 P,
    -psi^3 P) = (P, [X]P, [X^2]P, [X^3]P), X = X_ABS, for P of order r,
    stacked [4, L]. The (0, 0) identity maps to itself. psi three times
    over is ONE scan of decompress.g2_psi_graph: one site of its two
    multiplications in the compiled module."""
    import jax

    from charon_tpu.ops.decompress import g2_psi_graph

    def step(a, _):
        a = g2_psi_graph(ctx, a)
        return a, a

    _, (xs, ys) = lax.scan(step, affine, None, length=3)
    ys = T.fp2_select(jnp.asarray([[True], [False], [True]]), T.fp2_neg(ctx, ys), ys)
    head = lambda one, rest: jnp.concatenate([one[None], rest])
    return jax.tree_util.tree_map(head, affine, (xs, ys))


# T[m] = T[m - 2^k] + T[2^k] for every m with more than one bit, in an
# order that finds both terms made: (m, m - 2^k, 2^k), k the top bit of m
_TABLE_ADDS = tuple(
    (m, m - (1 << k), 1 << k)
    for k in (1, 2, 3)
    for m in range((1 << k) + 1, 2 << k)
)


def g2_scalar_mul_psi(ctx: ModCtx, fr_ctx: ModCtx, affine, scalars):
    """[k]P for FLAT affine G2 lanes [L] of order r (or the (0, 0)
    identity) and raw Fr scalars [L, n_limbs]: projective points [L].

    The table T[m] = sum_{bit i of m} B_i over the four bases (T[0] the
    identity, so the complete add needs no mask) is filled by one scan of
    11 adds on L lanes — ONE add site in the compiled module: an unrolled
    complete G2 add is 13-17 MB of generated code, which a warm boot
    loads (PERF.md §6, PRs 42 and 46) — then ONE lax.scan of 64 steps:
    acc = 2 acc + T[m_j], m_j the j-th bits of the four digits, MSB first.
    The coefficients are public (share indices), so nothing secret indexes
    the table."""
    import jax

    f = g2_ops(ctx)
    tree = jax.tree_util.tree_map
    lanes = scalars.shape[0]
    identity = tree(
        lambda a: limb.match_vary(a, affine[0][0]), point_identity(f, (lanes,))
    )
    bases = affine_to_point(f, g2_psi_bases(ctx, affine))  # [4, L] leaves
    table = tree(  # [16, L]: the identity but for T[2^i] = B_i
        lambda i, b: jnp.stack(
            [b[m.bit_length() - 1] if m in (1, 2, 4, 8) else i for m in range(16)]
        ),
        identity, bases,
    )

    def fill(table, add):
        at = lambda i: tree(lambda t: lax.dynamic_index_in_dim(t, i, keepdims=False), table)
        made = point_add(f, at(add[1]), at(add[2]))
        return tree(lambda t, v: lax.dynamic_update_index_in_dim(t, v, add[0], 0), table, made), None

    table, _ = lax.scan(fill, table, jnp.asarray(_TABLE_ADDS, jnp.int32))

    bits = _scalar_bits_msb(fr_ctx, psi_digits(fr_ctx, scalars), PSI_STEPS)
    weights = jnp.asarray([1, 2, 4, 8], jnp.int32)[None, :, None]
    picks = jnp.sum(bits.astype(jnp.int32) * weights, axis=1, dtype=jnp.int32)  # [64, L]
    entry = jnp.arange(16, dtype=jnp.int32)[:, None]

    def step(acc, pick):
        hit = (pick[None, :] == entry)[..., None]  # [16, L, 1]
        addend = tree(
            lambda t: jnp.sum(jnp.where(hit, t, jnp.zeros((), t.dtype)), axis=0, dtype=t.dtype),
            table,
        )
        return point_add(f, point_double(f, acc), addend), None

    acc, _ = lax.scan(step, identity, picks)
    return acc


# ---------------------------------------------------------------------------
# Host <-> device packing (affine Python-int points, identity = None)
# ---------------------------------------------------------------------------


def g1_pack(ctx: ModCtx, points):
    """Iterable of affine G1 points ((x, y) ints or None) -> device affine
    pair of Montgomery limb arrays, identity encoded as (0, 0)."""
    xs, ys = [], []
    for pt in points:
        if pt is None:
            xs.append(0)
            ys.append(0)
        else:
            xs.append(pt[0])
            ys.append(pt[1])
    return (
        jnp.asarray(limb.pack_mont_host(ctx, xs)),
        jnp.asarray(limb.pack_mont_host(ctx, ys)),
    )


def g1_unpack(ctx: ModCtx, affine) -> list:
    xs = limb.unpack_mont_host(ctx, affine[0])
    ys = limb.unpack_mont_host(ctx, affine[1])
    return [None if x == 0 and y == 0 else (x, y) for x, y in zip(xs, ys)]


def g2_pack(ctx: ModCtx, points):
    """Iterable of affine G2 points (((x0,x1),(y0,y1)) or None) -> device
    affine pair of Fp2 elements."""
    xs, ys = [], []
    for pt in points:
        if pt is None:
            xs.append((0, 0))
            ys.append((0, 0))
        else:
            xs.append(pt[0])
            ys.append(pt[1])
    return (T.fp2_pack(ctx, xs), T.fp2_pack(ctx, ys))


def g2_unpack(ctx: ModCtx, affine) -> list:
    xs = T.fp2_unpack(ctx, affine[0])
    ys = T.fp2_unpack(ctx, affine[1])
    return [
        None if x == (0, 0) and y == (0, 0) else (x, y)
        for x, y in zip(xs, ys)
    ]


def fr_pack(ctx: ModCtx, scalars) -> jnp.ndarray:
    """Raw (non-Montgomery) scalar packing for the bit-schedule kernels."""
    return jnp.asarray(limb.ctx_pack(ctx, [s % ctx.modulus for s in scalars]))
