"""BLS signature VERIFICATION in plain Python, beside reference.py and from
the same public specifications: the per-lane oracle for a verify flush.

  - draft-irtf-cfrg-bls-signature-05: KeyValidate (2.5), CoreVerify (2.7):
    the signature decodes and lies in the subgroup, the public key decodes,
    is not the identity and lies in the subgroup, and
    e(pk, H(m)) == e(g1, signature)
  - the ZCash serialization of compressed points, read back (reference.py
    writes it)
  - the pairing: the tower Fp2 -> Fp6 = Fp2[v]/(v^3 - (1 + i)) -> Fp12 =
    Fp6[w]/(w^2 - v), the optimal ate Miller loop over the curve's parameter
    on the sextic twist y^2 = x^3 + 4(1 + i) (M-type: a twist point (x, y)
    stands for (x / w^2, y / w^3)), and the final exponentiation
    (p^12 - 1) / r as (p^6 - 1) * ((p^6 + 1) / r)

reference.py has no pairing, so it can say that an aggregate is the group
signature and cannot say which LANE of a flush is bad; this can. It imports
reference.py alone: nothing of charon_tpu, no library. It decides no run's
`correct`. tests/test_reference_verify.py anchors it to the consensus
specification's `bls/verify` known answers; a verification takes 0.2-0.3
seconds."""

from __future__ import annotations

from benchmark import reference as ref
from benchmark.reference import FP, FP2, P, R, f2_add, f2_inv, f2_mul, f2_neg, f2_sqr, f2_sub

# --- Fp6 = Fp2[v] / (v^3 - xi), xi = 1 + i: elements (a0, a1, a2) ------------

F6_ZERO = (ref.F2_ZERO,) * 3
F6_ONE = (ref.F2_ONE, ref.F2_ZERO, ref.F2_ZERO)


def _xi(a):
    """(1 + i) * a in Fp2."""
    return ((a[0] - a[1]) % P, (a[0] + a[1]) % P)


def f6_add(a, b):
    return tuple(f2_add(x, y) for x, y in zip(a, b))


def f6_sub(a, b):
    return tuple(f2_sub(x, y) for x, y in zip(a, b))


def f6_neg(a):
    return tuple(f2_neg(x) for x in a)


def f6_mul(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    return (
        f2_add(f2_mul(a0, b0), _xi(f2_add(f2_mul(a1, b2), f2_mul(a2, b1)))),
        f2_add(f2_add(f2_mul(a0, b1), f2_mul(a1, b0)), _xi(f2_mul(a2, b2))),
        f2_add(f2_add(f2_mul(a0, b2), f2_mul(a1, b1)), f2_mul(a2, b0)),
    )


def f6_mul_v(a):
    """v * a: v^3 = xi."""
    return (_xi(a[2]), a[0], a[1])


def f6_inv(a):
    a0, a1, a2 = a
    t0 = f2_sub(f2_sqr(a0), _xi(f2_mul(a1, a2)))
    t1 = f2_sub(_xi(f2_sqr(a2)), f2_mul(a0, a1))
    t2 = f2_sub(f2_sqr(a1), f2_mul(a0, a2))
    den = f2_inv(f2_add(f2_mul(a0, t0), _xi(f2_add(f2_mul(a2, t1), f2_mul(a1, t2)))))
    return (f2_mul(t0, den), f2_mul(t1, den), f2_mul(t2, den))


# --- Fp12 = Fp6[w] / (w^2 - v): elements (c0, c1) ---------------------------

F12_ONE = (F6_ONE, F6_ZERO)


def f12_mul(a, b):
    t0, t1 = f6_mul(a[0], b[0]), f6_mul(a[1], b[1])
    cross = f6_mul(f6_add(a[0], a[1]), f6_add(b[0], b[1]))
    return (f6_add(t0, f6_mul_v(t1)), f6_sub(f6_sub(cross, t0), t1))


def f12_conj(a):
    """a^(p^6): w -> -w."""
    return (a[0], f6_neg(a[1]))


def f12_inv(a):
    den = f6_inv(f6_sub(f6_mul(a[0], a[0]), f6_mul_v(f6_mul(a[1], a[1]))))
    return (f6_mul(a[0], den), f6_neg(f6_mul(a[1], den)))


def f12_pow(a, e: int):
    out = F12_ONE
    for bit in bin(e)[2:]:
        out = f12_mul(out, out)
        if bit == "1":
            out = f12_mul(out, a)
    return out


# --- the pairing --------------------------------------------------------------


def _line(t, slope, p):
    """The line through twist point `t` with twist slope `slope`, at the G1
    point `p`, times w^3 (which lies in a proper subfield, so the final
    exponentiation removes it): untwisted, the slope is slope / w and
    l(p) = y_p - slope * x_p / w + (slope * x_t - y_t) / w^3."""
    (xt, yt), (xp, yp) = t, p
    c0 = (f2_sub(f2_mul(slope, xt), yt), f2_neg(ref.f2_scale(slope, xp)), ref.F2_ZERO)
    return (c0, (ref.F2_ZERO, (yp % P, 0), ref.F2_ZERO))


def miller_loop(p, q):
    """f_{|x|, q}(p), conjugated because the parameter x is negative; `p`
    affine on G1, `q` affine on the twist, neither the identity. Vertical
    lines lie in a proper subfield and are left out."""
    f, t = F12_ONE, q
    for bit in bin(ref.X_ABS)[3:]:
        slope = f2_mul(ref.f2_scale(f2_sqr(t[0]), 3), f2_inv(ref.f2_scale(t[1], 2)))
        f = f12_mul(f12_mul(f, f), _line(t, slope, p))
        x3 = f2_sub(f2_sqr(slope), ref.f2_scale(t[0], 2))
        t = (x3, f2_sub(f2_mul(slope, f2_sub(t[0], x3)), t[1]))
        if bit == "1":
            slope = f2_mul(f2_sub(q[1], t[1]), f2_inv(f2_sub(q[0], t[0])))
            f = f12_mul(f, _line(t, slope, p))
            x3 = f2_sub(f2_sub(f2_sqr(slope), t[0]), q[0])
            t = (x3, f2_sub(f2_mul(slope, f2_sub(t[0], x3)), t[1]))
    return f12_conj(f)


HARD = (P**6 + 1) // R
assert (P**6 + 1) % R == 0


def final_exponentiation(f):
    """f^((p^12 - 1) / r): first p^6 - 1 (a conjugate over an inverse), then
    (p^6 + 1) / r by square and multiply."""
    return f12_pow(f12_mul(f12_conj(f), f12_inv(f)), HARD)


def pairing(p, q):
    """e(p, q) for affine p on G1 and q on G2 (the twist); the identity
    pairs to one."""
    if p is None or q is None:
        return F12_ONE
    return final_exponentiation(miller_loop(p, q))


# --- points read back from their compressed form ----------------------------


def _flags(data: bytes, size: int):
    if len(data) != size or not data[0] & 0x80:
        raise ref.ReferenceError_("not a compressed point of this group")
    infinity, largest = bool(data[0] & 0x40), bool(data[0] & 0x20)
    body = bytes([data[0] & 0x1F]) + data[1:]
    if infinity and (largest or any(body)):
        raise ref.ReferenceError_("the identity with other bits set")
    return infinity, largest, body


def g1_decompress(data: bytes):
    """Affine (x, y) on the curve, None for the identity; not yet checked
    for the subgroup."""
    infinity, largest, body = _flags(data, 48)
    if infinity:
        return None
    x = int.from_bytes(body, "big")
    y = None if x >= P else ref.fp_sqrt((x * x * x + 4) % P)
    if y is None:
        raise ref.ReferenceError_("no point of the curve has this x")
    return (x, P - y if (y > ref.HALF_P) != largest else y)


def g2_decompress(data: bytes):
    infinity, largest, body = _flags(data, 96)
    if infinity:
        return None
    x1, x0 = int.from_bytes(body[:48], "big"), int.from_bytes(body[48:], "big")
    y = None if x0 >= P or x1 >= P else ref.f2_sqrt(
        f2_add(f2_mul(f2_sqr((x0, x1)), (x0, x1)), (4, 4)))
    if y is None:
        raise ref.ReferenceError_("no point of the twist has this x")
    if (y[1] > ref.HALF_P or (y[1] == 0 and y[0] > ref.HALF_P)) != largest:
        y = f2_neg(y)
    return ((x0, x1), y)


def in_subgroup(field, xy) -> bool:
    """r * point is the identity."""
    return ref.pt_mul(field, ref.pt_jacobian(field, xy), R) is None


# --- the scheme ---------------------------------------------------------------


def key_validate(pubkey: bytes):
    """KeyValidate: the affine point, or None where the key is refused."""
    try:
        pk = g1_decompress(pubkey)
    except ref.ReferenceError_:
        return None
    if pk is None or not in_subgroup(FP, pk):
        return None
    return pk


def verify(pubkey: bytes, message: bytes, signature: bytes) -> bool:
    """CoreVerify. The two pairings are compared as one product,
    e(pk, H(m)) * e(-g1, signature) == 1, which shares the final
    exponentiation."""
    try:
        sig = g2_decompress(signature)
    except ref.ReferenceError_:
        return False
    if sig is not None and not in_subgroup(FP2, sig):
        return False
    pk = key_validate(pubkey)
    if pk is None:
        return False
    h = ref.pt_affine(FP2, ref.hash_to_g2(message))
    f = F12_ONE if h is None else miller_loop(pk, h)
    if sig is not None:
        g1 = ref.G1_GEN
        f = f12_mul(f, miller_loop((g1[0], P - g1[1]), sig))
    return final_exponentiation(f) == F12_ONE
