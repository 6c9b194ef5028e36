"""The signing root of a builder registration, in plain Python from the
builder specification (`ValidatorRegistrationV1`, `DOMAIN_APPLICATION_BUILDER`)
and the consensus specification's SSZ: the object signed is the hash tree
root of the four fields (fee_recipient Bytes20, gas_limit uint64, timestamp
uint64, pubkey Bytes48), under compute_domain(DOMAIN_APPLICATION_BUILDER,
genesis fork version, a ZERO genesis validators root) — the builder
specification's `compute_domain(DOMAIN_APPLICATION_BUILDER)` with both
defaults, whatever fork the chain is at. Beside reference.py, whose merkle
hash it uses and which it imports alone: nothing of `charon_tpu`.

Assumed, and said where it is used (duties/registration.py): the fork
version the harness hands a kind's `expected` is the lock's, and the
benchmark's clusters are made at the genesis fork, so it IS the genesis fork
version; a cluster made at a later fork would need the genesis one handed
in."""

from __future__ import annotations

from benchmark.reference import _h, _u64

DOMAIN_APPLICATION_BUILDER = bytes.fromhex("00000001")


def registration_root(fields) -> bytes:
    """hash_tree_root(ValidatorRegistrationV1): four leaves. A Bytes20 is one
    chunk, right-padded; a uint64 its eight little-endian bytes, padded
    (`reference._u64`); a Bytes48 two chunks (the second half-empty) hashed
    into one root."""
    fee_recipient, gas_limit, timestamp, pubkey = fields
    if len(fee_recipient) != 20 or len(pubkey) != 48:
        raise ValueError("a fee recipient of 20 bytes and a public key of 48")
    leaves = (fee_recipient + bytes(12), _u64(gas_limit),
              _u64(timestamp), _h(pubkey[:32], pubkey[32:] + bytes(16)))
    return _h(_h(leaves[0], leaves[1]), _h(leaves[2], leaves[3]))


def registration_signing_root(fields, genesis_fork_version: bytes,
                              domain_type: bytes = DOMAIN_APPLICATION_BUILDER) -> bytes:
    """hash_tree_root(SigningData(object_root, domain)): the domain is the
    type's four bytes and the first 28 of hash_tree_root(ForkData(genesis
    fork version, zero root))."""
    if len(genesis_fork_version) != 4 or len(domain_type) != 4:
        raise ValueError("a fork version and a domain type of 4 bytes")
    fork_data_root = _h(genesis_fork_version + bytes(28), bytes(32))
    return _h(registration_root(fields), domain_type + fork_data_root[:28])
