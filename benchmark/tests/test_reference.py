"""The plain reference against public known answers, and its internal
consistency. Cross-checks against the harness's signer and the program's
SSZ come last and are named as such: they anchor nothing."""

from __future__ import annotations

import hashlib
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import reference as R  # noqa: E402

# RFC 9380 appendix J.10.1, BLS12381G2_XMD:SHA-256_SSWU_RO_: msg, P.x, P.y
RFC_DST = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
RFC_J_10_1 = [
    (b"",
     (0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
      0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D),
     (0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
      0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6)),
    (b"abc",
     (0x02C2D18E033B960562AAE3CAB37A27CE00D80CCD5BA4B7FE0E7A210245129DBEC7780CCC7954725F4168AFF2787776E6,
      0x139CDDBCCDC5E91B9623EFD38C49F81A6F83F175E80B06FC374DE9EB4B41DFE4CA3A230ED250FBE3A2ACF73A41177FD8),
     (0x1787327B68159716A37440985269CF584BCB1E621D3A7202BE6EA05C4CFE244AEB197642555A0645FB87BF7466B2BA48,
      0x00AA65DAE3C8D732D10ECD2C50F8A1BAF3001578F71C694E03866E9F3D49AC1E1CE70DD94A733534F106D4CEC0EDDD16)),
]
# expand_message_xmd, RFC 9380 appendix K.1 (SHA-256), len_in_bytes 0x20
XMD_DST = b"QUUX-V01-CS02-with-expander-SHA256-128"
XMD_K1 = [
    (b"", "68a985b87eb6b46952128911f2a4412bbc302a9d759667f87f7a21d803f07235"),
    (b"abc", "d8ccab23b5985ccea865c6c97b6e5b8350e794e603b4b97902f53a8a0d605615"),
]
# the generators' compressed forms (ZCash serialization; draft-irtf-cfrg-
# pairing-friendly-curves, and every BLS12-381 library's documentation)
G1_COMPRESSED = ("97f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac58"
                 "6c55e83ff97a1aeffb3af00adb22c6bb")
G2_COMPRESSED = ("93e02b6052719f607dacd3a088274f65596bd0d09920b61ab5da61bbdc7f5049"
                 "334cf11213945d57e5ac7d055d042b7e024aa2b2f08f0a91260805272dc51051"
                 "c6e47ad4fa403b02b4510b647ae3d1770bac0326a805bbefd48056c8c121bdb8")
H_EFF_RFC = 0xBC69F08F2EE75B3584C6A0EA91B352888E2A8E9145AD7689986FF031508FFE1329C2F178731DB956D82BF015D1212B02EC0EC69D7477C1AE954CBC06689F6A359894C0ADEBBF6B4E8020005AAA95551


# ethereum/bls12-381-tests (the consensus specification's `bls/sign` cases,
# ciphersuite BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_): secret, message, signature
ETH2_SIGN = [
    ("328388aff0d4a5b7dc9205abd374e7e98f3cd9f3418edb4eafda5fb16473d216", "ab" * 32,
     "ae82747ddeefe4fd64cf9cedb9b04ae3e8a43420cd255e3c7cd06a8d88b7c7f8638543719981c5d16fa3527c"
     "468c25f0026704a6951bde891360c7e8d12ddee0559004ccdbe6046b55bae1b257ee97f7cdb955773d7cf29a"
     "df3ccbb9975e4eb9"),
    ("47b8192d77bf871b62e87859d653922725724a5c031afeabc60bcef5ff665138", "00" * 32,
     "b23c46be3a001c63ca711f87a005c200cc550b9429d5f4eb38d74322144f1b63926da3388979e5321012fb1a"
     "0526bcd100b5ef5fe72628ce4cd5e904aeaa3279527843fae5ca9ca675f4f51ed8f83bbf7155da9ecc966310"
     "0a885d5dc6df96d9"),
]
ETH2_PUBKEY = ("263dbd792f5b1be47ed85f8938c0f29586af0d3ac7b977f21c278fe1462040e3",
               "a491d1b0ecd9bb917989f0e74f0dea0422eac4a873e5e2644f368dffb9a6e20f"
               "d6e10c1b77654d067c0618f6e5a7f79a")


@pytest.mark.parametrize("secret,msg,want", ETH2_SIGN)
def test_sign_gives_the_consensus_specifications_answers(secret, msg, want):
    assert R.sign(bytes.fromhex(secret), bytes.fromhex(msg)).hex() == want


def test_the_public_key_of_the_specifications_test_secret():
    assert R.secret_to_public_key(bytes.fromhex(ETH2_PUBKEY[0])).hex() == ETH2_PUBKEY[1]


@pytest.mark.parametrize("msg,want", XMD_K1)
def test_expand_message_xmd_gives_the_rfcs_answers(msg, want):
    assert R.expand_message_xmd(msg, XMD_DST, 0x20).hex() == want


@pytest.mark.parametrize("msg,x,y", RFC_J_10_1)
def test_hash_to_g2_gives_the_rfcs_answers(msg, x, y):
    assert R.pt_affine(R.FP2, R.hash_to_g2(msg, RFC_DST)) == (x, y)


def test_the_generators_are_on_their_curves_of_order_r_and_serialize_as_published():
    assert R.on_g1(R.G1_GEN) and R.on_g2(R.G2_GEN)
    assert R.pt_mul(R.FP, R.pt_jacobian(R.FP, R.G1_GEN), R.R) is None
    assert R.pt_mul(R.FP2, R.pt_jacobian(R.FP2, R.G2_GEN), R.R) is None
    assert R.g1_compress(R.G1_GEN).hex() == G1_COMPRESSED
    assert R.g2_compress(R.G2_GEN).hex() == G2_COMPRESSED
    one = (1).to_bytes(32, "big")
    assert R.secret_to_public_key(one).hex() == G1_COMPRESSED
    # the sign flag: the negated generator has the larger y
    neg = (R.G2_GEN[0], R.f2_neg(R.G2_GEN[1]))
    assert R.g2_compress(neg).hex() == "b" + G2_COMPRESSED[1:]
    assert R.g1_compress(None)[0] == 0xC0 and R.g2_compress(None)[0] == 0xC0


def test_the_isogeny_lands_on_the_curve_and_the_fast_cofactor_clearing_is_h_eff():
    assert R.H_EFF == H_EFF_RFC
    for i in range(3):
        u = R.hash_to_field_fp2(b"point %d" % i, RFC_DST)[0]
        x, y = q = R.map_to_curve_sswu(u)
        on_iso = R.f2_add(R.f2_add(R.f2_mul(R.f2_sqr(x), x), R.f2_mul(R.SSWU_A, x)), R.SSWU_B)
        assert R.f2_sqr(y) == on_iso
        e2 = R.iso_map(q)
        assert R.on_g2(e2)
        p = R.pt_jacobian(R.FP2, e2)
        fast = R.clear_cofactor_g2(p)
        assert R.pt_affine(R.FP2, fast) == R.pt_affine(R.FP2, R.pt_mul(R.FP2, p, R.H_EFF))
        assert R.pt_mul(R.FP2, fast, R.R) is None  # in the subgroup


def test_a_signature_is_linear_in_the_secret_and_shares_recombine_to_it():
    """sign(a) + sign(b) = sign(a + b), and Lagrange at zero over any t
    shares of threshold_split gives the group secret: the two facts the
    comparison `aggregates_differ` stands on."""
    msg = hashlib.sha256(b"root").digest()
    a, b = R.seeded_scalar("a"), R.seeded_scalar("b")
    h = R.hash_to_g2(msg)
    lhs = R.pt_add(R.FP2, R.pt_mul(R.FP2, h, a), R.pt_mul(R.FP2, h, b))
    assert R.g2_compress(R.pt_affine(R.FP2, lhs)) == R.sign(((a + b) % R.R).to_bytes(32, "big"), msg)
    secret = R.seeded_scalar("group").to_bytes(32, "big")
    shares = R.threshold_split(secret, 7, 4, "split")
    for idx in ([1, 2, 3, 4], [2, 4, 5, 7]):
        acc = 0
        for i in idx:
            lam = 1
            for j in idx:
                if j != i:
                    lam = lam * j % R.R * pow(j - i, -1, R.R) % R.R
            acc = (acc + lam * int.from_bytes(shares[i], "big")) % R.R
        assert acc.to_bytes(32, "big") == secret


def test_secrets_out_of_range_are_refused():
    for bad in (bytes(32), R.R.to_bytes(32, "big"), b"short"):
        with pytest.raises(R.ReferenceError_):
            R.sign(bad, b"m")


def test_the_signing_root_of_an_attestation_from_its_raw_fields():
    """The all-zero AttestationData under an all-zero domain: its tree is
    three levels of zero hashes, so the answer is one anyone can recompute."""
    z = [bytes(32)]
    for _ in range(3):
        z.append(hashlib.sha256(z[-1] + z[-1]).digest())
    fields = (0, 0, bytes(32), 0, bytes(32), 0, bytes(32))
    # the two checkpoints are not zero leaves: each is H(zero || zero) = z[1]
    left = hashlib.sha256(hashlib.sha256(z[0] + z[0]).digest()
                          + hashlib.sha256(z[0] + z[1]).digest()).digest()
    right = hashlib.sha256(hashlib.sha256(z[1] + z[0]).digest() + z[1]).digest()
    assert R.attestation_data_root(fields) == hashlib.sha256(left + right).digest()
    domain = R.DOMAIN_BEACON_ATTESTER + hashlib.sha256(bytes(64)).digest()[:28]
    assert R.attestation_signing_root(fields, bytes(4), bytes(32)) == hashlib.sha256(
        R.attestation_data_root(fields) + domain).digest()
    # a field moved is a root moved
    assert R.attestation_data_root((1,) + fields[1:]) != R.attestation_data_root(fields)


# -- cross-checks: agreement with code the reference does not stand on --------


def test_cross_check_the_harness_signer_agrees_on_seeded_keys_and_roots():
    from benchmark import signer

    for i in range(12):
        secret = R.seeded_scalar("bench-group", 2147483659, i).to_bytes(32, "big")
        root = hashlib.sha256(b"root %d" % i).digest()
        assert R.sign(secret, root) == signer.sign(secret, root)
        assert R.secret_to_public_key(secret) == signer.secret_to_public_key(secret)


def test_cross_check_the_programs_ssz_agrees_on_a_plans_attestations():
    from benchmark import manifest, traffic
    from charon_tpu.core.eth2data import Attestation, AttestationData, Checkpoint, SignedData
    from charon_tpu.eth2util.signing import ForkInfo

    cell = manifest.load_cell(REPO, "dv-4of7-1k.attest-slot")
    plan = traffic.make_plan(cell.config, cell.traffic, 2147483659)
    attester = manifest.load_duty("attester")
    fork = ForkInfo(genesis_validators_root=hashlib.sha256(b"gvr").digest(),
                    fork_version=bytes(4), genesis_fork_version=bytes(4))
    for slot, ci in ((0, 0), (33, 5), (1000, 30)):
        f = attester.fields(plan, slot, ci)
        data = AttestationData(slot=f[0], index=f[1], beacon_block_root=f[2],
                               source=Checkpoint(f[3], f[4]), target=Checkpoint(f[5], f[6]))
        assert data.hash_tree_root() == R.attestation_data_root(f)
        theirs = SignedData("attestation", Attestation((True,), data)).signing_root(
            fork, slot // plan.slots_per_epoch)
        assert theirs == R.attestation_signing_root(f, bytes(4), fork.genesis_validators_root)
