"""Multi-process networked DKG: N separate OS processes run the CLI
`dkg` command over localhost TCP and must produce identical lock files.

This is the reference's core multi-operator trust story
(ref: dkg/dkg.go:82 Run, dkg/sync/client.go:31 sync protocol,
dkg/frostp2p.go FROST transport) exercised end-to-end: create-enr ->
create-dkg -> sign-definition x n -> dkg x n (subprocesses).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

from charon_tpu.cmd import cli
from charon_tpu.testutil.compose import _free_ports

REPO = Path(__file__).resolve().parent.parent

N = 4


@pytest.mark.slow
def test_networked_dkg_multiprocess(tmp_path):
    dirs = [tmp_path / f"node{i}" for i in range(N)]

    # 1. each operator generates an identity (in-process, fast)
    enrs = []
    for d in dirs:
        d.mkdir()
        assert cli.main(["create-enr", "--data-dir", str(d)]) == 0
        key = cli._load_node_key(d)
        from charon_tpu.app import k1util

        enrs.append("enr:" + k1util.public_key_to_bytes(key.public_key()).hex())

    # 2. one operator creates the definition; everyone signs it
    def_path = tmp_path / "cluster-definition.json"
    assert (
        cli.main(
            [
                "create-dkg",
                "--name",
                "proc-test",
                "--num-validators",
                "1",
                "--operator-enrs",
                ",".join(enrs),
                "--output",
                str(def_path),
            ]
        )
        == 0
    )
    for d in dirs:
        assert (
            cli.main(
                [
                    "sign-definition",
                    "--definition-file",
                    str(def_path),
                    "--data-dir",
                    str(d),
                ]
            )
            == 0
        )

    # 3. the ceremony itself: N separate OS processes over localhost TCP
    ports = _free_ports(N)
    peers = ",".join(f"127.0.0.1:{p}" for p in ports)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # tests never claim an accelerator
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                "-m",
                "charon_tpu.cmd.cli",
                "dkg",
                "--definition-file",
                str(def_path),
                "--data-dir",
                str(dirs[i]),
                "--peers",
                peers,
                "--no-tpu",
                "--timeout",
                "90",
            ],
            env=env,
            cwd=str(REPO),
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(N)
    ]
    outs = [p.communicate(timeout=180) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"dkg process failed:\n{out}\n{err}"

    # 4. identical lock files with a valid aggregate + keystores per node
    locks = [
        json.loads((d / "cluster-lock.json").read_text()) for d in dirs
    ]
    assert all(lock == locks[0] for lock in locks[1:])
    assert locks[0]["signature_aggregate"].startswith("0x")
    assert len(locks[0]["node_signatures"]) == N
    for d in dirs:
        keys = list((d / "validator_keys").glob("keystore-*.json"))
        assert len(keys) == 1

    # deposit-data.json: identical across nodes, launchpad shape
    deposits = [
        json.loads((d / "deposit-data.json").read_text()) for d in dirs
    ]
    assert all(dd == deposits[0] for dd in deposits[1:])
    assert deposits[0][0]["deposit_data_root"]

    # 5. the lock verifies: aggregate signature + every node signature
    from charon_tpu.app import k1util as k1
    from charon_tpu.cluster.lock import ClusterLock

    lock = ClusterLock.load(str(dirs[0] / "cluster-lock.json"))
    lock_hash = lock.lock_hash()
    pubkeys = [bytes.fromhex(e.split(":")[-1]) for e in enrs]
    for pk, sig_hex in zip(pubkeys, lock.node_signatures):
        assert k1.verify_bytes(pk, lock_hash, bytes.fromhex(sig_hex))
