"""The threshold scheme in plain Python, beside reference.py and from the
same public descriptions: Shamir's split of a secret over the scalar field
(reference.threshold_split: shares 1..n of a polynomial of degree t - 1
drawn from a seed), a partial signature (the BLS signature by a share), and
the recombination: Lagrange interpolation at 0 over WHICHEVER t share
indices signed, in the exponent — sum over i of lambda_i * partial_i, with
lambda_i = product over j != i of j / (j - i) mod r.

It imports reference.py and nothing else: no code of the program, no
library. It does not decide `correct` (reference.sign by the group secret
does: the group signature is unique whichever t shares made it); the tests
hold the program's served recombination to it on share-index sets that
are not 1..t (tests/test_threshold_subsets.py)."""

from __future__ import annotations

from benchmark import reference as ref

split = ref.threshold_split  # (secret, n, t, *seed) -> {index: 32-byte share}
partial_sign = ref.sign  # (share, data) -> 96 bytes: a share signs as a secret does


def lagrange_at_zero(indices) -> dict[int, int]:
    """{i: lambda_i mod r} for distinct non-zero share indices."""
    indices = [int(i) for i in indices]
    if len(set(indices)) != len(indices) or any(not 0 < i < ref.R for i in indices):
        raise ref.ReferenceError_(f"share indices {indices}: distinct and 1-based")
    out = {}
    for i in indices:
        num = den = 1
        for j in indices:
            if j != i:
                num = num * j % ref.R
                den = den * (j - i) % ref.R
        out[i] = num * pow(den, -1, ref.R) % ref.R
    return out


def recombine_secret(shares: dict[int, bytes]) -> bytes:
    """The secret from t (or more) of its shares: the split, undone."""
    lam = lagrange_at_zero(shares)
    total = sum(lam[i] * int.from_bytes(s, "big") for i, s in shares.items()) % ref.R
    return total.to_bytes(32, "big")


def g2_decompress(data: bytes):
    """ZCash compressed G2 -> affine ((x0, x1), (y0, y1)); None is the
    point at infinity. The inverse of reference.g2_compress."""
    if len(data) != 96 or not data[0] & 0x80:
        raise ref.ReferenceError_("not a compressed G2 point")
    if data[0] & 0x40:
        if any(data[1:]) or data[0] != 0xC0:
            raise ref.ReferenceError_("malformed point at infinity")
        return None
    x1 = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:48], "big")
    x0 = int.from_bytes(data[48:], "big")
    if x0 >= ref.P or x1 >= ref.P:
        raise ref.ReferenceError_("coordinate out of range")
    x = (x0, x1)
    y = ref.f2_sqrt(ref.f2_add(ref.f2_mul(ref.f2_sqr(x), x), (4, 4)))
    if y is None:
        raise ref.ReferenceError_("not on the curve")
    largest = y[1] > ref.HALF_P or (y[1] == 0 and y[0] > ref.HALF_P)
    if largest != bool(data[0] & 0x20):
        y = ref.f2_neg(y)
    return (x, y)


def recombine(partials: dict[int, bytes]) -> bytes:
    """The group signature from {share index: partial signature}: every
    partial given is used, so give exactly the t that are to count."""
    lam = lagrange_at_zero(partials)
    acc = None
    for i, sig in partials.items():
        point = ref.pt_jacobian(ref.FP2, g2_decompress(sig))
        acc = ref.pt_add(ref.FP2, acc, ref.pt_mul(ref.FP2, point, lam[i]))
    return ref.g2_compress(ref.pt_affine(ref.FP2, acc))
