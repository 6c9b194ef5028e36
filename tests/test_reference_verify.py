"""benchmark/reference_verify.py, the plain per-lane oracle (ISSUE 35): the
consensus specification's `bls/verify` known answers (ethereum/bls12-381-tests:
the `bls/sign` triples of benchmark/tests/test_reference.py verify; another
key, another message, a tampered signature and the identity do not), the
pairing's own laws, the points it reads back, and that it imports
reference.py alone."""

from __future__ import annotations

import ast
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import reference as R, reference_verify as V  # noqa: E402
from benchmark.tests.test_reference import ETH2_PUBKEY, ETH2_SIGN  # noqa: E402

# the public keys of the specification's three test secrets, as published
PUBKEY = {
    "263dbd792f5b1be47ed85f8938c0f29586af0d3ac7b977f21c278fe1462040e3":
        "a491d1b0ecd9bb917989f0e74f0dea0422eac4a873e5e2644f368dffb9a6e20f"
        "d6e10c1b77654d067c0618f6e5a7f79a",
    "47b8192d77bf871b62e87859d653922725724a5c031afeabc60bcef5ff665138":
        "b301803f8b5ac4a1133581fc676dfedc60d891dd5fa99028805e5ea5b08d3491"
        "af75d0707adab3b70c6a6a580217bf81",
    "328388aff0d4a5b7dc9205abd374e7e98f3cd9f3418edb4eafda5fb16473d216":
        "b53d21a4cfd562c469cc81514d4ce5a6b577d8403d32a394dc265dd190b47fa9"
        "f829fdd7963afdf972e5e77854051f6f",
}
(SK_A, MSG_A, SIG_A), (SK_B, MSG_B, SIG_B) = ETH2_SIGN
G1_IDENTITY, G2_IDENTITY = "c0" + "00" * 47, "c0" + "00" * 95

# the specification's `bls/verify` cases: pubkey, message, signature, output
VERIFY = {
    "valid_ab": (PUBKEY[SK_A], MSG_A, SIG_A, True),
    "valid_00": (PUBKEY[SK_B], MSG_B, SIG_B, True),
    "wrong_pubkey": (PUBKEY[SK_B], MSG_A, SIG_A, False),
    "wrong_message": (PUBKEY[SK_A], MSG_B, SIG_A, False),
    "tampered_signature": (PUBKEY[SK_A], MSG_A, SIG_A[:-8] + "ffffffff", False),
    "infinity_pubkey_and_infinity_signature": (G1_IDENTITY, MSG_A, G2_IDENTITY, False),
    "infinity_signature": (PUBKEY[SK_A], MSG_A, G2_IDENTITY, False),
}


@pytest.mark.parametrize("case", sorted(VERIFY))
def test_verify_gives_the_consensus_specifications_answers(case):
    pubkey, message, signature, want = VERIFY[case]
    assert V.verify(*(bytes.fromhex(x) for x in (pubkey, message, signature))) is want


def test_the_published_public_keys_are_the_test_secrets():
    assert PUBKEY[ETH2_PUBKEY[0]] == ETH2_PUBKEY[1]
    for secret, want in PUBKEY.items():
        assert R.secret_to_public_key(bytes.fromhex(secret)).hex() == want


def _times(field, xy, k):
    return R.pt_affine(field, R.pt_mul(field, R.pt_jacobian(field, xy), k))


def test_the_pairing_is_bilinear_not_degenerate_and_of_order_r():
    e = V.pairing(R.G1_GEN, R.G2_GEN)
    assert e != V.F12_ONE and V.f12_pow(e, R.R) == V.F12_ONE
    assert V.pairing(_times(R.FP, R.G1_GEN, 5), _times(R.FP2, R.G2_GEN, 7)) == V.f12_pow(e, 35)
    assert V.pairing(None, R.G2_GEN) == V.pairing(R.G1_GEN, None) == V.F12_ONE
    assert V.f12_mul(e, V.f12_inv(e)) == V.F12_ONE


def test_compressed_points_are_read_back_and_bad_ones_refused():
    sk, sig = bytes.fromhex(SK_A), bytes.fromhex(SIG_A)
    assert R.g1_compress(V.g1_decompress(R.secret_to_public_key(sk))) == R.secret_to_public_key(sk)
    assert R.g2_compress(V.g2_decompress(sig)) == sig
    assert V.g1_decompress(bytes.fromhex(G1_IDENTITY)) is None
    assert V.g2_decompress(bytes.fromhex(G2_IDENTITY)) is None
    for bad in (sig[:95], bytes([sig[0] & 0x7F]) + sig[1:],  # short; not compressed
                bytes([0xC0]) + bytes(94) + b"\x01",  # the identity with a bit set
                bytes([0x9F]) + b"\xff" * 95):  # x beyond the modulus
        with pytest.raises(R.ReferenceError_):
            V.g2_decompress(bad)
        assert V.verify(R.secret_to_public_key(sk), bytes.fromhex(MSG_A), bad) is False


def test_a_point_of_the_twist_outside_the_subgroup_is_refused():
    """What the isogeny gives before the cofactor is cleared lies on the
    twist and, but for one chance in the cofactor, outside G2."""
    u0, _u1 = R.hash_to_field_fp2(b"outside", R.DST_POP)
    stray = R.iso_map(R.map_to_curve_sswu(u0))
    assert R.on_g2(stray) and not V.in_subgroup(R.FP2, stray)
    assert V.in_subgroup(R.FP2, R.G2_GEN) and V.in_subgroup(R.FP, R.G1_GEN)
    pk = R.secret_to_public_key(bytes.fromhex(SK_A))
    assert V.verify(pk, bytes.fromhex(MSG_A), R.g2_compress(stray)) is False


def test_the_harness_forgery_is_well_formed_and_fails_only_the_pairing():
    """benchmark/serve.py's `wrong_key` partial: the signature of a seeded
    foreign secret on another message. It decodes and lies in G2 (so the
    RLC program's decode mask lets it through) and verifies under its own
    key and message, and under no validator's."""
    from benchmark import signer

    foreign = R.seeded_scalar("forger", 3500000009, 0).to_bytes(32, "big")
    forged = signer.sign(foreign, b"forged" + bytes(26))
    assert forged == R.sign(foreign, b"forged" + bytes(26))
    assert V.in_subgroup(R.FP2, V.g2_decompress(forged))
    assert V.verify(R.secret_to_public_key(foreign), b"forged" + bytes(26), forged) is True
    share = R.seeded_scalar("share", 1).to_bytes(32, "big")
    assert V.verify(R.secret_to_public_key(share), b"\x07" * 32, forged) is False


def test_it_agrees_with_the_programs_second_engine_on_seeded_lanes():
    """A cross-check, not the anchor: the C++ engine behind the host tbls
    rung answers the same on honest and forged lanes."""
    from charon_tpu.tbls.native_impl import NativeImpl

    native = NativeImpl()
    lanes = []
    for n in range(3):
        secret = R.seeded_scalar("lane", n).to_bytes(32, "big")
        root = bytes([n + 1]) * 32
        sig = R.sign(secret, root)
        lanes.append((R.secret_to_public_key(secret), root, sig))
    lanes.append((lanes[0][0], lanes[0][1], lanes[1][2]))  # another lane's signature
    assert [V.verify(*lane) for lane in lanes] == native.verify_batch(lanes) \
        == [True, True, True, False]


def test_it_imports_reference_py_alone():
    tree = ast.parse((REPO / "benchmark/reference_verify.py").read_text())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            modules.add(node.module)
    assert modules == {"__future__", "benchmark", "benchmark.reference"}
