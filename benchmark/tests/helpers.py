"""What the tests share: a root of their own (the benchmark's data files
copied into a temporary directory beside a BENCHMARK.json the test may add
to), and the patches `run.Rehearsal` carries into a run: the control of
`correct`, and the two ways the tests break the timed path underneath."""

from __future__ import annotations

import asyncio
import json
import shutil
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA_DIRS = ("configs", "mixes", "metrics", "readers", "duties")
RECORDED = REPO / "benchmark/tests/data/tiny.xplane.pb"  # by record_trace.py, on a v5e
RECORDED_WINDOW_S = 0.8027191162109375  # the session's start to its stop, in that recording

REHEARSAL = {
    "name": "rehearsal",
    "source": "tests only: the served path's control flow at a tiny size, host-only",
    "operators": 4, "threshold": 3, "validators": 14,
    "slots_per_epoch": 4, "slot_duration_s": 3.0, "key_table_keys": 56,
    "keystore_kdf_c": 2,
    "node": {"use_tpu_tbls": False, "crypto_plane": "off"},
    "env": {},
    "programs": ["verify_rlc_dec@16", "step_rlc_dec@4", "g1dec@512"],
    "reduced": {},
}


def make_root(tmp: Path, rehearsal: bool = False) -> Path:
    bench = tmp / "benchmark"
    bench.mkdir(parents=True)
    for d in DATA_DIRS:
        shutil.copytree(REPO / "benchmark" / d, bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    if rehearsal:
        (bench / "configs" / "rehearsal.json").write_text(json.dumps(REHEARSAL))
        manifest["configs"].append({
            "name": "rehearsal", "source": REHEARSAL["source"],
            "file": "benchmark/configs/rehearsal.json", "reduced": [], "why": "tests"})
        manifest["workloads"].append({
            "name": "rehearsal.attest-slot", "config": "rehearsal",
            "traffic": "attest-slot", "chips": 1, "why": "tests"})
        for m in manifest["per_layer"]:
            m["workloads"] = m["workloads"] + ["rehearsal.attest-slot"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def fake_trace() -> None:
    """The recorded trace stands in for the profiler, which has no device
    plane on the CPU: a traced run then reads it as its own window's."""
    from benchmark import tracered

    tracered.start = lambda jax: {"wall": time.time()}
    tracered.stop = lambda handle, note=None: tracered.reduce_file(
        str(RECORDED), handle["wall"], RECORDED_WINDOW_S)


# -- patches: each takes the run's Server once its node is built ------------


def unchecked_recombine(server) -> None:
    """THE CONTROL of `correct`: in the node's place, a recombination that
    checks nothing — the harness's signer library, Lagrange at zero, no
    verification of the partials against their public shares and none of
    the group signature (the step that would save `program_s.recombine`)
    — fed what that check is there to catch: the first partial of every
    duty taken for the share of operator n + 1. It breaks the
    configuration's guarantee `aggregate_is_group_signature`; every duty's
    aggregate then differs from the plain reference's."""
    from benchmark import signer

    sigagg, n = server.node.sigagg, server.plan.operators

    def recombine(partial_maps):
        out = []
        for pmap in partial_maps:
            first = min(pmap)
            out.append(signer.recombine_unchecked(
                {(n + 1 if i == first else i): sig for i, sig in pmap.items()}))
        return out

    async def via_plane(_duty, _epoch, _pubkeys, partial_maps, _templates):
        return await asyncio.to_thread(recombine, partial_maps)

    def via_tbls(_epoch, _pubkeys, partial_maps, _templates):
        return recombine(partial_maps)

    sigagg._aggregate_via_plane = via_plane
    sigagg._aggregate_via_tbls = via_tbls


def altered_aggregate(server) -> None:
    """The timed path broken underneath: the node's threshold aggregation
    flips one byte of its answer where it is produced."""
    from charon_tpu.tbls.native_impl import NativeImpl

    sound = NativeImpl.threshold_aggregate

    def broken(self, partials):
        sig = sound(self, partials)
        return sig[:20] + bytes([sig[20] ^ 1]) + sig[21:]

    NativeImpl.threshold_aggregate = broken


def trusted_peers(server) -> None:
    """The timed path broken at its entry: the node takes its peers'
    partial sets unverified, so the mix's forged set is never rejected."""
    from charon_tpu.core import parsigex

    async def trust(_self, _duty, _signed_set, **_kw):
        return True

    parsigex.Eth2Verifier.verify_async = trust


PATCHES = {f.__name__: f for f in (unchecked_recombine, altered_aggregate, trusted_peers)}
