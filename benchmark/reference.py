"""The plain reference: BLS12-381 signing in plain Python, written from
the public specifications and sharing no code with the repo.

  - draft-irtf-cfrg-bls-signature-05 (the eth2 ciphersuite
    BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_): sign = sk * H(m)
  - RFC 9380: hash_to_curve for BLS12381G2_XMD:SHA-256_SSWU_RO_
    (expand_message_xmd, simplified SWU on the 3-isogenous curve, the
    isogeny of appendix E.3, cofactor clearing of appendix G.3)
  - the ZCash serialization of compressed points
  - the consensus specification's SSZ for AttestationData, compute_domain
    and compute_signing_root

It imports nothing of charon_tpu and loads no library: the group secret's
own signature on a root IS the aggregate any t valid partials must
recombine to, byte for byte. tests/test_reference.py anchors it to the
RFC's known answers."""

from __future__ import annotations

import hashlib

# --- the curve (parameters as the specifications print them) ---------------

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X_ABS = 0xD201000000010000  # the curve's parameter is -X_ABS
HALF_P = (P - 1) // 2

G1_GEN = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
G2_GEN = (
    (0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
     0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E),
    (0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
     0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE),
)
DST_POP = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"


class ReferenceError_(ValueError):
    pass


# --- Fp2 = Fp[i] / (i^2 + 1), elements (c0, c1) ----------------------------

F2_ZERO, F2_ONE = (0, 0), (1, 0)


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    t0, t1 = a[0] * b[0], a[1] * b[1]
    return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)


def f2_sqr(a):
    return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)


def f2_scale(a, k: int):
    return (a[0] * k % P, a[1] * k % P)


def f2_inv(a):
    n = pow(a[0] * a[0] + a[1] * a[1], -1, P)
    return (a[0] * n % P, -a[1] * n % P)


def f2_conj(a):
    """The Frobenius map of Fp2."""
    return (a[0], -a[1] % P)


def f2_pow(a, e: int):
    out = F2_ONE
    for bit in bin(e)[2:]:
        out = f2_sqr(out)
        if bit == "1":
            out = f2_mul(out, a)
    return out


def fp_sqrt(a: int):
    """p = 3 mod 4."""
    s = pow(a, (P + 1) // 4, P)
    return s if s * s % P == a % P else None


def f2_sqrt(a):
    """A square root of `a`, or None: through the norm, p = 3 mod 4."""
    a0, a1 = a
    if a1 == 0:
        s = fp_sqrt(a0)
        if s is not None:
            return (s, 0)
        s = fp_sqrt(-a0 % P)  # (0, s)^2 = -s^2
        return None if s is None else (0, s)
    alpha = fp_sqrt((a0 * a0 + a1 * a1) % P)
    if alpha is None:
        return None
    half = pow(2, -1, P)
    x0 = fp_sqrt((a0 + alpha) * half % P)
    if x0 is None:
        x0 = fp_sqrt((a0 - alpha) * half % P)
    if x0 is None:
        return None
    x = (x0, a1 * pow(2 * x0, -1, P) % P)
    return x if f2_sqr(x) == (a0 % P, a1 % P) else None


def f2_sgn0(a) -> int:
    """RFC 9380 section 4.1, m = 2."""
    return (a[0] & 1) | ((a[0] == 0) & (a[1] & 1))


# --- Jacobian points over a field given by its operations -------------------


class _Field:
    def __init__(self, add, sub, mul, sqr, inv, zero, one):
        self.add, self.sub, self.mul, self.sqr, self.inv = add, sub, mul, sqr, inv
        self.zero, self.one = zero, one


FP = _Field(lambda a, b: (a + b) % P, lambda a, b: (a - b) % P, lambda a, b: a * b % P,
            lambda a: a * a % P, lambda a: pow(a, -1, P), 0, 1)
FP2 = _Field(f2_add, f2_sub, f2_mul, f2_sqr, f2_inv, F2_ZERO, F2_ONE)


def pt_double(f: _Field, p):
    """dbl-2009-l, a = 0. None is the point at infinity."""
    if p is None:
        return None
    x, y, z = p
    if y == f.zero:
        return None
    a, b = f.sqr(x), f.sqr(y)
    c = f.sqr(b)
    d = f.sub(f.sub(f.sqr(f.add(x, b)), a), c)
    d = f.add(d, d)
    e = f.add(f.add(a, a), a)
    x3 = f.sub(f.sqr(e), f.add(d, d))
    c8 = f.add(c, c)
    c8 = f.add(c8, c8)
    c8 = f.add(c8, c8)
    y3 = f.sub(f.mul(e, f.sub(d, x3)), c8)
    yz = f.mul(y, z)
    return (x3, y3, f.add(yz, yz))


def pt_add(f: _Field, p, q):
    """add-2007-bl."""
    if p is None:
        return q
    if q is None:
        return p
    x1, y1, z1 = p
    x2, y2, z2 = q
    z1z1, z2z2 = f.sqr(z1), f.sqr(z2)
    u1, u2 = f.mul(x1, z2z2), f.mul(x2, z1z1)
    s1, s2 = f.mul(f.mul(y1, z2), z2z2), f.mul(f.mul(y2, z1), z1z1)
    if u1 == u2:
        return pt_double(f, p) if s1 == s2 else None
    h = f.sub(u2, u1)
    i = f.sqr(f.add(h, h))
    j = f.mul(h, i)
    r = f.sub(s2, s1)
    r = f.add(r, r)
    v = f.mul(u1, i)
    x3 = f.sub(f.sub(f.sqr(r), j), f.add(v, v))
    s1j = f.mul(s1, j)
    y3 = f.sub(f.mul(r, f.sub(v, x3)), f.add(s1j, s1j))
    z3 = f.mul(f.sub(f.sub(f.sqr(f.add(z1, z2)), z1z1), z2z2), h)
    return (x3, y3, z3)


def pt_neg(f: _Field, p):
    return None if p is None else (p[0], f.sub(f.zero, p[1]), p[2])


def pt_mul(f: _Field, p, k: int):
    """k * p, double-and-add from the top bit; k >= 0."""
    out = None
    for bit in bin(k)[2:]:
        out = pt_double(f, out)
        if bit == "1":
            out = pt_add(f, out, p)
    return out


def pt_affine(f: _Field, p):
    if p is None:
        return None
    zi = f.inv(p[2])
    zi2 = f.sqr(zi)
    return (f.mul(p[0], zi2), f.mul(p[1], f.mul(zi2, zi)))


def pt_jacobian(f: _Field, xy):
    return None if xy is None else (xy[0], xy[1], f.one)


def on_g1(xy) -> bool:
    x, y = xy
    return y * y % P == (x * x * x + 4) % P


def on_g2(xy) -> bool:
    x, y = xy
    return f2_sqr(y) == f2_add(f2_mul(f2_sqr(x), x), (4, 4))


# --- RFC 9380: hash to G2 ---------------------------------------------------


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    """Section 5.3.1 with SHA-256 (b = 32 bytes, block = 64 bytes)."""
    ell = -(-n // 32)
    if ell > 255 or n > 65535 or len(dst) > 255:
        raise ReferenceError_("expand_message_xmd: length")
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\x00" + dst_prime).digest()
    blocks = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(x ^ y for x, y in zip(b0, blocks[-1]))
        blocks.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(blocks)[:n]


def hash_to_field_fp2(msg: bytes, dst: bytes, count: int = 2):
    """Section 5.2: m = 2, L = 64."""
    uniform = expand_message_xmd(msg, dst, count * 2 * 64)
    return [
        tuple(int.from_bytes(uniform[64 * (j + 2 * i): 64 * (j + 2 * i + 1)], "big") % P
              for j in range(2))
        for i in range(count)
    ]


# the curve E': y^2 = x^3 + A'x + B', 3-isogenous to E2 (section 8.8.2)
SSWU_A = (0, 240)
SSWU_B = (1012, 1012)
SSWU_Z = (-2 % P, -1 % P)


def map_to_curve_sswu(u):
    """Section 6.6.2, the straight-line description; a point of E'."""
    a, b, z = SSWU_A, SSWU_B, SSWU_Z
    zu2 = f2_mul(z, f2_sqr(u))
    tv1 = f2_add(f2_sqr(zu2), zu2)
    if tv1 == F2_ZERO:
        x1 = f2_mul(b, f2_inv(f2_mul(z, a)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(b), f2_inv(a)), f2_add(F2_ONE, f2_inv(tv1)))

    def g(x):
        return f2_add(f2_add(f2_mul(f2_sqr(x), x), f2_mul(a, x)), b)

    y = f2_sqrt(g(x1))
    x = x1
    if y is None:
        x = f2_mul(zu2, x1)
        y = f2_sqrt(g(x))
        if y is None:
            raise ReferenceError_("sswu: neither candidate is a square")
    if f2_sgn0(u) != f2_sgn0(y):
        y = f2_neg(y)
    return (x, y)


# appendix E.3: the 3-isogeny E' -> E2, coefficients k_(i,j), lowest degree first
ISO_X_NUM = (
    (0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0,
     0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1,
     0),
)
ISO_X_DEN = (
    (0, -72 % P),  # the RFC prints p - 72
    (12, -12 % P),
    F2_ONE,
)
ISO_Y_NUM = (
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10,
     0),
)
ISO_Y_DEN = (
    (-432 % P, -432 % P),
    (0, -216 % P),
    (18, -18 % P),
    F2_ONE,
)


def _poly(coeffs, x):
    acc = F2_ZERO
    for c in reversed(coeffs):
        acc = f2_add(f2_mul(acc, x), c)
    return acc


def iso_map(xy):
    """E' -> E2 (affine; the kernel's points go to infinity)."""
    x, y = xy
    xd, yd = _poly(ISO_X_DEN, x), _poly(ISO_Y_DEN, x)
    if xd == F2_ZERO or yd == F2_ZERO:
        return None
    return (f2_mul(_poly(ISO_X_NUM, x), f2_inv(xd)),
            f2_mul(y, f2_mul(_poly(ISO_Y_NUM, x), f2_inv(yd))))


# appendix G.3: psi and the fast cofactor clearing; constants from their
# definitions, not from a table
PSI_CX = f2_inv(f2_pow((1, 1), (P - 1) // 3))
PSI_CY = f2_inv(f2_pow((1, 1), (P - 1) // 2))
PSI2_CX = pow(pow(2, (P - 1) // 3, P), -1, P)
# section 8.8.2: h_eff = 3 * (z^2 - 1) * h2, h2 the cofactor of G2
_Z = -X_ABS
H2 = (_Z**8 - 4 * _Z**7 + 5 * _Z**6 - 4 * _Z**4 + 6 * _Z**3 - 4 * _Z**2 - 4 * _Z + 13) // 9
H_EFF = 3 * (_Z * _Z - 1) * H2


def psi(p):
    if p is None:
        return None
    return (f2_mul(PSI_CX, f2_conj(p[0])), f2_mul(PSI_CY, f2_conj(p[1])), f2_conj(p[2]))


def psi2(p):
    if p is None:
        return None
    return (f2_scale(p[0], PSI2_CX), f2_neg(p[1]), p[2])


def _mul_by_z(p):
    """z * p for the curve's (negative) parameter z."""
    return pt_neg(FP2, pt_mul(FP2, p, X_ABS))


def clear_cofactor_g2(p):
    """clear_cofactor_bls12381_g2 of appendix G.3 (= h_eff * p)."""
    f = FP2
    t1 = _mul_by_z(p)
    t2 = psi(p)
    t3 = psi2(pt_double(f, p))
    t3 = pt_add(f, t3, pt_neg(f, t2))
    t2 = pt_add(f, t1, t2)
    t2 = _mul_by_z(t2)
    t3 = pt_add(f, t3, t2)
    t3 = pt_add(f, t3, pt_neg(f, t1))
    return pt_add(f, t3, pt_neg(f, p))


def hash_to_g2(msg: bytes, dst: bytes = DST_POP):
    """hash_to_curve of BLS12381G2_XMD:SHA-256_SSWU_RO_ (Jacobian)."""
    u0, u1 = hash_to_field_fp2(msg, dst)
    q0 = pt_jacobian(FP2, iso_map(map_to_curve_sswu(u0)))
    q1 = pt_jacobian(FP2, iso_map(map_to_curve_sswu(u1)))
    return clear_cofactor_g2(pt_add(FP2, q0, q1))


# --- serialization (ZCash: compressed, infinity and sign flags) -------------


def g1_compress(xy) -> bytes:
    if xy is None:
        return bytes([0xC0]) + bytes(47)
    x, y = xy
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if y > HALF_P else 0)
    return bytes(out)


def g2_compress(xy) -> bytes:
    if xy is None:
        return bytes([0xC0]) + bytes(95)
    (x0, x1), (y0, y1) = xy
    out = bytearray(x1.to_bytes(48, "big") + x0.to_bytes(48, "big"))
    largest = y1 > HALF_P or (y1 == 0 and y0 > HALF_P)
    out[0] |= 0x80 | (0x20 if largest else 0)
    return bytes(out)


# --- the signature scheme ---------------------------------------------------


def _secret_scalar(secret: bytes) -> int:
    if len(secret) != 32:
        raise ReferenceError_("secret must be 32 bytes")
    k = int.from_bytes(secret, "big")
    if not 0 < k < R:
        raise ReferenceError_("secret out of range")
    return k


def secret_to_public_key(secret: bytes) -> bytes:
    return g1_compress(pt_affine(FP, pt_mul(FP, pt_jacobian(FP, G1_GEN), _secret_scalar(secret))))


def sign(secret: bytes, data: bytes) -> bytes:
    return g2_compress(pt_affine(FP2, pt_mul(FP2, hash_to_g2(data), _secret_scalar(secret))))


# --- SSZ and the signing root of an attestation ------------------------------

DOMAIN_BEACON_ATTESTER = bytes.fromhex("01000000")


def _h(a: bytes, b: bytes) -> bytes:
    return hashlib.sha256(a + b).digest()


def _u64(n: int) -> bytes:
    return n.to_bytes(8, "little") + bytes(24)


def attestation_data_root(fields) -> bytes:
    """hash_tree_root(AttestationData) from the raw fields (slot, index,
    beacon_block_root, source epoch, source root, target epoch, target
    root): five leaves merkleized under eight."""
    slot, index, block_root, s_epoch, s_root, t_epoch, t_root = fields
    zero = bytes(32)
    leaves = [_u64(slot), _u64(index), block_root,
              _h(_u64(s_epoch), s_root), _h(_u64(t_epoch), t_root)]
    left = _h(_h(leaves[0], leaves[1]), _h(leaves[2], leaves[3]))
    right = _h(_h(leaves[4], zero), _h(zero, zero))
    return _h(left, right)


def attestation_signing_root(fields, fork_version: bytes, genesis_validators_root: bytes) -> bytes:
    """compute_signing_root(data, compute_domain(DOMAIN_BEACON_ATTESTER,
    fork_version, genesis_validators_root))."""
    fork_data_root = _h(fork_version + bytes(28), genesis_validators_root)
    domain = DOMAIN_BEACON_ATTESTER + fork_data_root[:28]
    return _h(attestation_data_root(fields), domain)


# --- key material from the seed ---------------------------------------------


def seeded_scalar(*parts) -> int:
    """A non-zero scalar from the parts' text (seed, validator, role)."""
    digest = hashlib.sha512("/".join(str(p) for p in parts).encode()).digest()
    return int.from_bytes(digest, "big") % (R - 1) + 1


def threshold_split(secret: bytes, total: int, threshold: int, *seed) -> dict[int, bytes]:
    """Shamir shares 1..total of `secret`, polynomial of degree
    threshold-1 with coefficients drawn from `seed`."""
    coeffs = [int.from_bytes(secret, "big") % R] + [
        seeded_scalar(*seed, "coeff", j) for j in range(1, threshold)
    ]
    shares = {}
    for i in range(1, total + 1):
        acc = 0
        for c in reversed(coeffs):
            acc = (acc * i + c) % R
        shares[i] = acc.to_bytes(32, "big")
    return shares
