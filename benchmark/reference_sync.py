"""The signing root of a sync-committee message, in plain Python from the
consensus specification (Altair, `get_sync_committee_message`): the object
signed is the beacon block root itself, under
compute_domain(DOMAIN_SYNC_COMMITTEE, fork_version, genesis_validators_root).
Beside reference.py, whose merkle hash it uses and which it imports alone:
nothing of `charon_tpu`."""

from __future__ import annotations

from benchmark.reference import _h

DOMAIN_SYNC_COMMITTEE = bytes.fromhex("07000000")


def sync_message_signing_root(block_root: bytes, fork_version: bytes,
                              genesis_validators_root: bytes) -> bytes:
    """hash_tree_root(SigningData(object_root=block_root, domain)): two
    leaves. The domain is the type's four bytes and the first 28 of
    hash_tree_root(ForkData(fork_version, genesis_validators_root))."""
    if len(block_root) != 32 or len(fork_version) != 4 or len(genesis_validators_root) != 32:
        raise ValueError("a block root and a genesis root of 32 bytes, a fork version of 4")
    fork_data_root = _h(fork_version + bytes(28), genesis_validators_root)
    return _h(block_root, DOMAIN_SYNC_COMMITTEE + fork_data_root[:28])
