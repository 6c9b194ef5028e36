"""Cluster material from --seed: keys split in process, and exactly the
node directory build_node reads. Copied from chip_smoke.py (PR 22) so a
later PR can change the smoke and not the yardstick. Secrets and Shamir
shares are the plain reference's (from --seed); the public keys and public
shares of the lock — the node's input — are made by the harness's signer."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import uuid
from pathlib import Path

from benchmark import reference, signer


@dataclasses.dataclass
class Cluster:
    lock: object
    k1_keys: list
    group_secrets: dict  # PubKey -> 32-byte group secret
    share_keys: list  # per operator: PubKey -> share secret
    validators: dict  # PubKey -> validator index (lock order)
    pubkeys: list  # validator index -> PubKey


def make_cluster(seed: int, operators: int, threshold: int, validators: int) -> Cluster:
    from charon_tpu.app import k1util
    from charon_tpu.cluster.definition import ClusterDefinition, Operator
    from charon_tpu.cluster.lock import ClusterLock, DistributedValidator
    from charon_tpu.core.types import pubkey_from_bytes
    from charon_tpu.eth2util import enr as enrlib

    n, t = operators, threshold
    k1_keys = [
        k1util.private_key_from_bytes(
            reference.seeded_scalar("bench-k1", seed, i).to_bytes(32, "big"))
        for i in range(n)
    ]
    defn = ClusterDefinition(
        name="benchmark",
        num_validators=validators,
        threshold=t,
        fork_version="0x00000000",
        operators=tuple(
            Operator(address=f"operator-{i}", enr=enrlib.new(k).to_string())
            for i, k in enumerate(k1_keys)
        ),
    )
    group_secrets, share_keys, dvs, pubkeys = {}, [dict() for _ in range(n)], [], []
    for v in range(validators):
        secret = reference.seeded_scalar("bench-group", seed, v).to_bytes(32, "big")
        gpk_bytes = signer.secret_to_public_key(secret)
        gpk = pubkey_from_bytes(gpk_bytes)
        group_secrets[gpk] = secret
        shares = reference.threshold_split(secret, n, t, "bench-split", seed, v)
        for idx, share in shares.items():
            share_keys[idx - 1][gpk] = share
        dvs.append(
            DistributedValidator(
                distributed_public_key="0x" + gpk_bytes.hex(),
                public_shares=tuple(
                    "0x" + signer.secret_to_public_key(shares[i]).hex()
                    for i in range(1, n + 1)
                ),
            )
        )
        pubkeys.append(gpk)
    lock = ClusterLock(definition=defn, validators=tuple(dvs))
    return Cluster(lock, k1_keys, group_secrets, share_keys,
                   {pk: i for i, pk in enumerate(pubkeys)}, pubkeys)


def write_node_dir(cluster: Cluster, node_index: int, data_dir: Path, kdf_c: int) -> None:
    """Exactly what build_node reads: the lock, the ENR key and this
    operator's share keystores — EIP-2335 files whose PBKDF2 work factor
    is the configuration's `keystore_kdf_c` (keystore.load_keys honours
    the file's own `c`)."""
    from charon_tpu.app import k1util
    from charon_tpu.eth2util import keystore

    data_dir.mkdir(parents=True, exist_ok=True)
    cluster.lock.save(str(data_dir / "cluster-lock.json"))
    (data_dir / "charon-enr-private-key").write_bytes(
        k1util.private_key_to_bytes(cluster.k1_keys[node_index])
    )
    keys_dir = data_dir / "validator_keys"
    keys_dir.mkdir()
    password = "benchmark"
    shares = cluster.share_keys[node_index]
    for i, gpk in enumerate(cluster.pubkeys):  # lock order
        salt = hashlib.sha256(f"salt/{i}".encode()).digest()
        iv = salt[:16]
        dk = keystore._kdf(password, salt, kdf_c)
        ciphertext = keystore._aes128ctr(dk[:16], iv, shares[gpk])
        ks = {
            "crypto": {
                "kdf": {"function": "pbkdf2",
                        "params": {"dklen": 32, "c": kdf_c, "prf": "hmac-sha256",
                                   "salt": salt.hex()},
                        "message": ""},
                "checksum": {"function": "sha256", "params": {},
                             "message": hashlib.sha256(dk[16:32] + ciphertext).hexdigest()},
                "cipher": {"function": "aes-128-ctr", "params": {"iv": iv.hex()},
                           "message": ciphertext.hex()},
            },
            "pubkey": "",
            "path": f"m/12381/3600/{i}/0/0",
            "uuid": str(uuid.UUID(bytes=salt[:16])),
            "version": 4,
        }
        (keys_dir / f"keystore-{i}.json").write_text(json.dumps(ks))
        (keys_dir / f"keystore-{i}.txt").write_text(password)
