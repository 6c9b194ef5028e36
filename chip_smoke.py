#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that charon-tpu still serves on the chip.

One process, one TPU chip (the default), no CPU mode:

    python chip_smoke.py            # one v5e chip: the served path
    python chip_smoke.py --chips 4  # four chips: the sharded plane only

Default run — ONE real distributed-validator node, built by
`app.run.build_node(Config(use_tpu_tbls=True, crypto_plane="on"))`, serves
three consecutive mainnet-shaped slots of a 4-of-7 cluster with 1,000
validators (BASELINE config 2): its six peers (host-only QBFT participants
signing with the native backend) send their partial signatures over the real
TCP p2p mesh into ParSigEx, its validator client submits this node's
partials over the ValidatorAPI HTTP router, and they flow SigAgg ->
SlotCoalescer -> SlotCryptoPlane -> device program -> verdicts and aggregate
bytes until the node's beacon mock holds the broadcast attestations. Every
aggregate is compared byte for byte with the native reference, one
flipped-byte partial must be rejected, and every fallback counter on the
path (resilient ladder, coalescer host rung, decode rung, warm-up python
lanes) must read zero: a green run that never used the device is a failure.

`--chips 4` runs only the plane's `_step_rlc`/`_verify_rlc` pair at one
bucket on a four-device mesh and on a one-device mesh, and compares them.

The last stdout line is the verdict the driver reads:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Earlier lines are one JSON object each (routing, compiled programs, slots,
counters); none of them is a benchmark result.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import concurrent.futures
import dataclasses
import functools
import hashlib
import json
import os
import socket
import sys
import tempfile
import threading
import time
from pathlib import Path

SEED = 22  # key material is derived from this; nothing else is random input


@dataclasses.dataclass(frozen=True)
class Sizes:
    """BASELINE config 2: one 4-of-7 cluster, 1,000 validators, mainnet
    slot shape (32 slots an epoch -> 31-32 attesters a slot)."""

    operators: int = 7
    threshold: int = 4
    validators: int = 1000
    slots_per_epoch: int = 32
    slot_duration: float = 12.0
    slots: int = 3
    # wide enough that the six peer sets and this node's own VC
    # submission (all signed right after the QBFT decision) share ONE
    # flush: a split wave would land on a second, uncompiled bucket
    window: float = 0.3
    window_max: float = 0.6


def emit(**obj) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


# persistent-cache hits by the thread that compiled: jax's monitoring
# event carries no program name, but it fires on the compiling thread,
# and every program here compiles on a thread of its own
_CACHE_HITS: collections.Counter = collections.Counter()


def _on_jax_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_HITS[threading.get_ident()] += 1


def timed_first_call(report, family: str, bucket: int, run) -> None:
    """Run a program's first dispatch on THIS thread and print its
    compile line (trace + compile or cache load + one execution)."""
    me = threading.get_ident()
    hits, t0 = _CACHE_HITS[me], time.monotonic()
    run()
    entry = {
        "family": family,
        "bucket": bucket,
        "seconds": round(time.monotonic() - t0, 1),
        "cache": "hit" if _CACHE_HITS[me] > hits else "miss",
    }
    report["programs"].append(entry)
    emit(phase="compile", **entry)


def first_calls_in_parallel(report, jobs) -> None:
    """jobs: [(family, bucket, run)], one thread each — traces share
    the GIL, XLA compiles run outside it. The first failure re-raises."""
    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        futures = [pool.submit(timed_first_call, report, *job) for job in jobs]
        for f in futures:
            f.result()


def device_info(jax) -> dict:
    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def routing(limb, ctx) -> dict:
    """The kernel routing in force for programs traced from now on."""
    from charon_tpu.ops import fptower
    from charon_tpu.ops import msm as MSM

    return {
        "limb_geometry": f"{ctx.n_limbs}x{ctx.limb_bits}b/{ctx.np_dtype.__name__}",
        "pallas": bool(limb._pallas_active(ctx)),
        "fp2_fusion": bool(fptower._FP2_FUSION and limb._pallas_active(ctx)),
        "msm": bool(MSM.msm_active()),
        "mxu": bool(limb._mxu_active(ctx)),
    }


# ---------------------------------------------------------------------------
# cluster material: keys split in process, nothing but what build_node reads
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cluster:
    lock: object
    k1_keys: list
    group_secrets: dict  # PubKey -> 32-byte group secret (reference signer)
    share_keys: list  # per operator: PubKey -> share secret
    validators: dict  # PubKey -> validator index


def make_cluster(sizes: Sizes, native) -> Cluster:
    from charon_tpu.app import k1util
    from charon_tpu.cluster.definition import ClusterDefinition, Operator
    from charon_tpu.cluster.lock import ClusterLock, DistributedValidator
    from charon_tpu.core.types import pubkey_from_bytes
    from charon_tpu.crypto.fields import R
    from charon_tpu.eth2util import enr as enrlib

    n, t = sizes.operators, sizes.threshold
    k1_keys = [k1util.generate_private_key() for _ in range(n)]
    defn = ClusterDefinition(
        name="chip-smoke",
        num_validators=sizes.validators,
        threshold=t,
        fork_version="0x00000000",
        operators=tuple(
            Operator(address=f"operator-{i}", enr=enrlib.new(k).to_string())
            for i, k in enumerate(k1_keys)
        ),
    )
    group_secrets, share_keys, dvs = {}, [dict() for _ in range(n)], []
    for v in range(sizes.validators):
        digest = hashlib.sha256(f"chip-smoke/{SEED}/{v}".encode()).digest()
        secret = (int.from_bytes(digest, "big") % (R - 1) + 1).to_bytes(32, "big")
        gpk_bytes = native.secret_to_public_key(secret)
        gpk = pubkey_from_bytes(gpk_bytes)
        group_secrets[gpk] = secret
        shares = native.threshold_split(secret, n, t)
        for idx, share in shares.items():
            share_keys[idx - 1][gpk] = share
        dvs.append(
            DistributedValidator(
                distributed_public_key="0x" + gpk_bytes.hex(),
                public_shares=tuple(
                    "0x" + native.secret_to_public_key(shares[i]).hex()
                    for i in range(1, n + 1)
                ),
            )
        )
    lock = ClusterLock(definition=defn, validators=tuple(dvs))
    validators = {
        pubkey_from_bytes(bytes.fromhex(dv.distributed_public_key[2:])): i
        for i, dv in enumerate(dvs)
    }
    return Cluster(lock, k1_keys, group_secrets, share_keys, validators)


def write_node_dir(cluster: Cluster, node_index: int, data_dir: Path) -> None:
    """Exactly what build_node reads: the lock, the ENR key and this
    operator's share keystores. The keystores are EIP-2335 files with
    the PBKDF2 work factor cut to 2 rounds (throwaway keys in a temp
    dir; 1,000 x 0.09 s at c=262144 is three minutes of set-up for
    nothing) — keystore.load_keys honours the file's own `c`."""
    import uuid

    from charon_tpu.app import k1util
    from charon_tpu.eth2util import keystore

    data_dir.mkdir(parents=True, exist_ok=True)
    cluster.lock.save(str(data_dir / "cluster-lock.json"))
    (data_dir / "charon-enr-private-key").write_bytes(
        k1util.private_key_to_bytes(cluster.k1_keys[node_index])
    )
    keys_dir = data_dir / "validator_keys"
    keys_dir.mkdir()
    password, c = "chip-smoke", 2
    shares = cluster.share_keys[node_index]
    for i, gpk in enumerate(cluster.validators):  # lock order
        salt = hashlib.sha256(f"salt/{i}".encode()).digest()
        iv = salt[:16]
        dk = keystore._kdf(password, salt, c)
        ciphertext = keystore._aes128ctr(dk[:16], iv, shares[gpk])
        ks = {
            "crypto": {
                "kdf": {
                    "function": "pbkdf2",
                    "params": {"dklen": 32, "c": c, "prf": "hmac-sha256",
                               "salt": salt.hex()},
                    "message": "",
                },
                "checksum": {
                    "function": "sha256",
                    "params": {},
                    "message": hashlib.sha256(dk[16:32] + ciphertext).hexdigest(),
                },
                "cipher": {
                    "function": "aes-128-ctr",
                    "params": {"iv": iv.hex()},
                    "message": ciphertext.hex(),
                },
            },
            "pubkey": "",
            "path": f"m/12381/3600/{i}/0/0",
            "uuid": str(uuid.UUID(bytes=salt[:16])),
            "version": 4,
        }
        (keys_dir / f"keystore-{i}.json").write_text(json.dumps(ks))
        (keys_dir / f"keystore-{i}.txt").write_text(password)


def free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def make_beacon(sizes: Sizes, validators: dict, genesis: float):
    """The repo's BeaconMock with mainnet-shaped duties: each validator
    attests ONCE an epoch (index mod 32 picks its slot) and no proposer
    or sync-committee duty is scheduled (cut: each would bring its own
    bucket shape and compile). Like the mock, every attester sits in a
    committee of its own, so a slot's wave carries 31-32 signing roots:
    DutyDB maps a submitted attestation back to its validator by (slot,
    attestation-data root) alone, so the ValidatorAPI cannot tell two of
    the cluster's validators in ONE committee apart (found at bring-up,
    PERF.md) — the common mainnet case, 31 validators spread over 64
    committees, is what this shape is."""
    from charon_tpu.testutil.beaconmock import BeaconMock

    class MainnetShapeBeacon(BeaconMock):
        async def attester_duties(self, epoch, vals):
            spe = self.slots_per_epoch
            by_slot: dict[int, list] = {}
            for i, (pubkey, vidx) in enumerate(sorted(vals.items())):
                by_slot.setdefault(i % spe, []).append((pubkey, vidx))
            return [
                dict(
                    slot=epoch * spe + s,
                    pubkey=pubkey,
                    validator_index=vidx,
                    committee_index=pos,
                    committee_length=1,
                    committees_at_slot=len(members),
                    validator_committee_index=0,
                )
                for s, members in sorted(by_slot.items())
                for pos, (pubkey, vidx) in enumerate(members)
            ]

        async def proposer_duties(self, epoch, vals):
            return []

        async def sync_duties(self, epoch, vals):
            return []

    return MainnetShapeBeacon(
        validators=dict(validators),
        genesis_time=genesis,
        slot_duration=sizes.slot_duration,
        slots_per_epoch=sizes.slots_per_epoch,
    )


# ---------------------------------------------------------------------------
# the six peers and the validator client: host-only code, native signer
# ---------------------------------------------------------------------------


class Gate:
    """Slots the smoke serves: [first, first + count). Peers and the VC
    stay silent outside it (the node boots, warms and compiles first);
    `forge` names (slot, share_idx) whose first partial gets one
    flipped byte."""

    def __init__(self) -> None:
        self.first: int | None = None
        self.count = 0
        self.forge: tuple[int, int] | None = None

    def open(self, slot: int) -> bool:
        return self.first is not None and (
            self.first <= slot < self.first + self.count
        )


def sign_attestations(native, fork, spe, share_keys, slot, duties):
    """duties: pubkey -> (AttestationData, committee_length,
    validator_committee_index) -> [Attestation] signed with the share
    keys (what testutil/validatormock.attest does, native signer)."""
    from charon_tpu.core.eth2data import Attestation, SignedData

    out = {}
    for pubkey, (data, length, pos) in duties.items():
        bits = tuple(i == pos for i in range(length))
        root = SignedData("attestation", Attestation(bits, data)).signing_root(
            fork, slot // spe
        )
        out[pubkey] = Attestation(bits, data, native.sign(share_keys[pubkey], root))
    return out


class HostPeer:
    """One of the six other operators: a real P2PNode with a real QBFT
    participant, scheduler and fetcher (so the cluster decides every
    duty), and instead of a VC + ValidatorAPI + SigAgg a native signer
    that sends this operator's partials through ParSigEx the moment the
    duty is decided. It verifies and aggregates nothing: tbls is
    process-global and belongs to the chip-backed node."""

    def __init__(self, sizes, cluster, index, ports, genesis, native, gate):
        self.sizes, self.cluster, self.index = sizes, cluster, index
        self.ports, self.genesis = ports, genesis
        self.native, self.gate = native, gate
        self.sent_sets = 0

    async def start(self) -> None:
        from charon_tpu.core.consensus_qbft import QBFTConsensus
        from charon_tpu.core.deadline import SlotClock
        from charon_tpu.core.fetcher import Fetcher
        from charon_tpu.core.parsigex import DutyGater, ParSigEx
        from charon_tpu.core.scheduler import Scheduler
        from charon_tpu.eth2util import enr
        from charon_tpu.p2p.adapters import TcpParSigTransport, TcpQbftNet
        from charon_tpu.p2p.transport import P2PNode, PeerSpec

        sizes, lock = self.sizes, self.cluster.lock
        op_pubkeys = [
            enr.pubkey_from_string(op.enr) for op in lock.definition.operators
        ]
        specs = [
            PeerSpec(index=i, pubkey=pk, host="127.0.0.1", port=self.ports[i])
            for i, pk in enumerate(op_pubkeys)
        ]
        self.p2p = P2PNode(
            self.index, self.cluster.k1_keys[self.index], specs, lock.lock_hash()
        )
        await self.p2p.start()
        clock = SlotClock(self.genesis, sizes.slot_duration)
        gater = DutyGater(clock, slots_per_epoch=sizes.slots_per_epoch)
        self.qbft = QBFTConsensus(
            TcpQbftNet(self.p2p),
            sizes.operators,
            privkey=self.cluster.k1_keys[self.index],
            pubkeys=op_pubkeys,
            gater=gater,
        )
        self.parsigex = ParSigEx(
            self.index + 1, TcpParSigTransport(self.p2p), gater=gater
        )
        beacon = make_beacon(sizes, self.cluster.validators, self.genesis)
        fetcher = Fetcher(beacon)
        fetcher.register_consensus(self.qbft.propose)
        self.scheduler = Scheduler(
            beacon, clock, self.cluster.validators,
            slots_per_epoch=sizes.slots_per_epoch,
        )
        self.scheduler.subscribe_duties(self._fetch)
        self.qbft.subscribe(self._decided)
        self._fetcher = fetcher
        self._task = asyncio.create_task(self.scheduler.run())

    async def _fetch(self, duty, defs) -> None:
        from charon_tpu.core.types import DutyType

        if duty.type == DutyType.ATTESTER:
            await self._fetcher.fetch(duty, defs)

    async def _decided(self, duty, unsigned_set) -> None:
        from charon_tpu.core.eth2data import ParSignedData, SignedData
        from charon_tpu.core.types import DutyType

        if duty.type != DutyType.ATTESTER or not self.gate.open(duty.slot):
            return
        share_idx = self.index + 1
        atts = sign_attestations(
            self.native,
            self.cluster.lock.fork_info(),
            self.sizes.slots_per_epoch,
            self.cluster.share_keys[self.index],
            duty.slot,
            {
                pk: (d.data, d.committee_length, d.validator_committee_index)
                for pk, d in unsigned_set.items()
            },
        )
        signed_set = {}
        for n, (pk, att) in enumerate(atts.items()):
            sig = att.signature
            if n == 0 and self.gate.forge == (duty.slot, share_idx):
                sig = sig[:10] + bytes([sig[10] ^ 0x40]) + sig[11:]
            signed_set[pk] = ParSignedData(
                SignedData("attestation", att, sig), share_idx
            )
        await self.parsigex.broadcast(duty, signed_set)
        self.sent_sets += 1

    async def stop(self) -> None:
        self.scheduler.stop()
        self._task.cancel()
        await self.p2p.stop()


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


async def serve(sizes: Sizes, use_tpu: bool = True) -> dict:
    """Boot the node and its peers, compile what the wave lands on,
    serve `sizes.slots` consecutive slots, and return the report
    (aggregates checked here; the device-path assertions are
    check_device_path's). `use_tpu=False` exists only for the scratch
    rehearsal driver — main() never passes it."""
    from charon_tpu import tbls
    from charon_tpu.app.run import Config, build_node
    from charon_tpu.core.types import DutyType
    from charon_tpu.tbls.native_impl import NativeImpl
    from charon_tpu.testutil.vapiclient import HttpVapiClient

    native = NativeImpl()
    report: dict = {"slots": [], "programs": []}
    t0 = time.monotonic()
    cluster = make_cluster(sizes, native)
    tmp = tempfile.TemporaryDirectory(prefix="chip_smoke_")
    node_dir = Path(tmp.name) / "node0"
    write_node_dir(cluster, 0, node_dir)
    emit(
        phase="cluster",
        operators=sizes.operators,
        threshold=sizes.threshold,
        validators=sizes.validators,
        keys_in_table=sizes.validators * (sizes.operators + 1),
        seconds=round(time.monotonic() - t0, 2),
    )

    ports = free_ports(sizes.operators)
    genesis = time.time()
    gate = Gate()
    beacon = make_beacon(sizes, cluster.validators, genesis)
    node = await build_node(
        Config(
            data_dir=str(node_dir),
            node_index=0,
            p2p_port=ports[0],
            peer_addrs=[("127.0.0.1", p) for p in ports],
            beacon_nodes=[beacon],
            slot_duration=sizes.slot_duration,
            slots_per_epoch=sizes.slots_per_epoch,
            genesis_time=genesis,
            use_tpu_tbls=use_tpu,
            crypto_plane="on",
            crypto_plane_window=sizes.window,
            crypto_plane_window_max=sizes.window_max,
            # boot cost stated, not hidden: no tuner, no default prewarm
            # ladder (fourteen program-shapes at minutes each) — the
            # smoke compiles exactly the shapes its wave lands on, below
            crypto_autotune="off",
            crypto_plane_prewarm="off",
            # what "auto" resolves to on a TPU backend, said outright
            crypto_plane_warmup="on",
        )
    )
    report["node"] = node
    report["tbls"] = tbls.get_implementation()
    peers = [
        HostPeer(sizes, cluster, i, ports, genesis, native, gate)
        for i in range(1, sizes.operators)
    ]
    fork = cluster.lock.fork_info()
    client = None

    async def on_duty(duty, defs):
        # this node's validator client: HTTP against the ValidatorAPI router
        if duty.type != DutyType.ATTESTER or not gate.open(duty.slot):
            return
        duties, data_by_committee = {}, {}
        for pk, d in defs.items():
            if d.committee_index not in data_by_committee:
                data_by_committee[d.committee_index] = (
                    await client.attestation_data(duty.slot, d.committee_index)
                )
            duties[pk] = (
                data_by_committee[d.committee_index],
                d.committee_length,
                d.validator_committee_index,
            )
        atts = sign_attestations(
            native, fork, sizes.slots_per_epoch, cluster.share_keys[0],
            duty.slot, duties,
        )
        await client.submit_attestations(list(atts.values()))

    node.scheduler.subscribe_duties(on_duty)

    coalescer = node.crypto_plane
    warm_stats: list[dict] = []
    flushes: list = []
    if coalescer is not None:
        # before anything else runs: compile what the wave lands on
        await compile_wave_programs(sizes, coalescer, report)
        hook_plane(coalescer, report, warm_stats, flushes)

    stop = asyncio.Event()
    life = None
    try:
        for p in peers:
            await p.start()
        vapi_port = await node.vapi_router.start("127.0.0.1", 0)
        client = HttpVapiClient(
            f"http://127.0.0.1:{vapi_port}", cluster.validators
        )
        life = asyncio.create_task(node.life.run(stop))
        if coalescer is not None and use_tpu:
            # the lifecycle's own start-up warm-up: the whole key table
            # through the device _g1dec program
            waited = time.monotonic()
            while not warm_stats:
                await asyncio.sleep(0.2)
                if life.done():
                    life.result()
                if time.monotonic() - waited > 600:
                    raise RuntimeError("start-up warm-up never reported")
            report["warmup"] = warm_stats[0]
            emit(phase="warmup", **{
                k: warm_stats[0][k] for k in ("pubkey", "message", "seconds")
                if k in warm_stats[0]
            })
        await serve_slots(
            sizes, cluster, node, beacon, peers, gate, flushes, native, report
        )
    finally:
        stop.set()
        if client is not None:
            await client.close()
        for p in peers:
            await p.stop()
        if life is not None:
            try:
                await asyncio.wait_for(life, timeout=15)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                life.cancel()
        tmp.cleanup()
    return report


def hook_plane(coalescer, report, warm_stats: list, flushes: list) -> None:
    """Chain the smoke's observers in front of the node's own hooks:
    warm-up stats, per-flush stats, and the first dispatch of any plane
    program the smoke did not compile itself (the warm-up's _g1dec; any
    other family showing up here means a flush left the compiled set)."""
    plane = coalescer.plane
    inner_warm, inner_stats = coalescer.warmup_hook, coalescer.stats_hook
    inner_prog = plane.on_program
    seen = {e["family"] for e in report["programs"]}

    def warm_hook(stats):
        warm_stats.append(stats)
        if inner_warm is not None:
            inner_warm(stats)

    def stats_hook(s):
        flushes.append((time.time(), s))
        if inner_stats is not None:
            inner_stats(s)

    def program_hook(family, seconds, lanes):
        if family not in seen:
            seen.add(family)
            entry = {
                "family": family,
                "bucket": plane.bucket_lanes(lanes),
                "seconds": round(seconds, 1),
                "cache": (
                    "hit" if _CACHE_HITS[threading.get_ident()] else "miss"
                ),
            }
            report["programs"].append(entry)
            emit(phase="compile", **entry)
        if inner_prog is not None:
            inner_prog(family, seconds, lanes)

    coalescer.warmup_hook = warm_hook
    coalescer.stats_hook = stats_hook
    plane.on_program = program_hook


async def compile_wave_programs(sizes: Sizes, coalescer, report) -> None:
    """Compile, through the plane's own prewarm entries, exactly the
    programs the all-valid wave lands on: the RLC tier of the verify
    program at the wave's bucket and of the recombine program at the
    slot's bucket. The per-lane attribution tier stays uncompiled (cut:
    the wave is all-valid and a flipped-byte partial fails device
    decompression on the RLC tier). The two are traced and compiled on
    two threads — XLA compiles outside the GIL."""
    plane = coalescer.plane
    per_slot = -(-sizes.validators // sizes.slots_per_epoch)
    wave = per_slot * sizes.operators  # six peer sets + this node's own
    dec = coalescer._decode_rung() == "device"
    wanted = (
        ("verify_rlc_dec", "step_rlc_dec") if dec else ("verify_rlc", "step_rlc")
    )
    todo = [
        (f"mesh/{family}", bucket, run)
        for _kind, family, bucket, run in plane.prewarm_programs(
            verify_lanes=(wave,), recombine_lanes=(per_slot,), decompress=dec
        )
        if family in wanted
    ]
    if len(todo) != len(wanted):
        raise RuntimeError(f"prewarm_programs did not offer {wanted}: {todo}")
    await asyncio.to_thread(first_calls_in_parallel, report, todo)
    report["wave_families"] = wanted


async def serve_slots(
    sizes, cluster, node, beacon, peers, gate, flushes, native, report
) -> None:
    from charon_tpu.core.eth2data import Attestation, SignedData

    clock = beacon.clock()
    spe = sizes.slots_per_epoch
    first = clock.slot_at(time.time()) + 2  # a whole slot of lead time
    gate.first, gate.count = first, sizes.slots
    last = first + sizes.slots - 1
    gate.forge = (last, sizes.operators)  # highest share index forges
    ordered = sorted(cluster.validators.items())
    fork = cluster.lock.fork_info()
    emit(phase="serve", first_slot=first, slots=sizes.slots)

    for slot in range(first, last + 1):
        members = [pk for i, (pk, _) in enumerate(ordered) if i % spe == slot % spe]
        start = clock.slot_start(slot)
        deadline = start + 2 * sizes.slot_duration
        while True:
            got = [a for a in beacon.attestations if a.data.slot == slot]
            if len(got) >= len(members) or time.time() >= deadline:
                break
            await asyncio.sleep(0.05)
        done = time.time()
        if len(got) != len(members):
            raise RuntimeError(
                f"slot {slot}: {len(got)}/{len(members)} attestations "
                f"broadcast by the deadline; {len(flushes)} flushes completed "
                "(a flush still compiling means the RLC tier rejected the "
                "wave and the attribution tier is being traced)"
            )
        # correctness, outside any timing: byte-equal to the native
        # reference (the group secret's own signature) and valid under
        # the group key
        for att in got:
            pk = members[att.data.index]  # committee index = position
            root = SignedData(
                "attestation", Attestation(att.aggregation_bits, att.data)
            ).signing_root(fork, slot // spe)
            want = native.sign(cluster.group_secrets[pk], root)
            if att.signature != want:
                raise RuntimeError(f"slot {slot}: aggregate != native reference")
            native.verify(bytes.fromhex(pk[2:]), root, att.signature)
        trigger = start + sizes.slot_duration / 3  # the attester offset
        slot_flushes = [s for ts, s in flushes if start <= ts < done + 0.001]
        entry = {
            "slot": slot,
            "duties": len(members),
            "aggregates_equal_reference": len(got),
            # from the attester trigger (1/3 slot) to the last broadcast
            "trigger_to_broadcast_seconds": round(done - trigger, 3),
            "flushes": [
                {
                    "lanes": s.lanes,
                    "jobs": s.jobs,
                    "decode_mode": s.decode_mode,
                    "device_seconds": round(
                        s.device_span[1] - s.device_span[0], 4
                    ) if s.device_span else None,
                    "flush_seconds": round(s.flush_seconds, 4),
                }
                for s in slot_flushes
            ],
        }
        report["slots"].append(entry)
        emit(phase="slot", **entry)

    forger = next(p for p in peers if p.index + 1 == sizes.operators)
    # ParSigEx bills a set that fails verification to the channel peer
    # in the node's evidence ledger (shared with SigAgg)
    rejected = node.sigagg.evidence.count(sizes.operators, "parsig_invalid")
    report["forged_rejected"] = rejected
    emit(
        phase="forgery",
        slot=last,
        share_idx=sizes.operators,
        sets_sent=forger.sent_sets,
        sets_rejected=rejected,
    )
    if rejected != 1:
        raise RuntimeError(
            f"the flipped-byte partial set was not rejected exactly once "
            f"(parsig_invalid evidence = {rejected})"
        )


def check_device_path(report: dict, jax) -> None:
    """Nothing may hide the device: every ladder on the path is still
    on its top rung and no lane was served by host code."""
    from charon_tpu.ops import limb
    from charon_tpu.tbls.tpu_impl import TPUImpl

    node = report["node"]
    coalescer = node.crypto_plane
    plane = coalescer.plane
    resilient = report["tbls"]
    tpu = resilient.impls[0]
    warm = report["warmup"]
    counters = {
        "ctx": plane.ctx.name,
        "pallas_active": bool(limb._pallas_active(plane.ctx)),
        "decode_rung": coalescer._decode_rung(),
        "resilient_fallback_calls": resilient.fallback_calls,
        "resilient_demotions": list(resilient.demotions),
        "host_fallback_flushes": coalescer.host_fallback_flushes,
        "pack_fallbacks": coalescer.pack_fallbacks,
        "degraded": coalescer._degraded,
        "degrade_rungs": list(tpu._degrade_rungs),
        "warmup_pubkey": warm.get("pubkey"),
        "flushes": coalescer.flushes,
        "lanes_flushed": coalescer.lanes_flushed,
        "compiled_programs": plane.jit_cache_size(),
    }
    stats = jax.devices()[0].memory_stats() or {}
    counters["peak_device_bytes"] = stats.get("peak_bytes_in_use")
    emit(phase="device_path", **counters)
    problems = []
    if not isinstance(tpu, TPUImpl):
        problems.append(f"top tbls rung is {type(tpu).__name__}")
    if plane.ctx is not limb.FP32 or plane.fr_ctx is not limb.FR32:
        problems.append("plane is not on the u32 limb geometry")
    if not counters["pallas_active"]:
        problems.append("pallas kernels are not active")
    if counters["decode_rung"] != "device":
        problems.append("coalescer decode rung is not device")
    if resilient.fallback_calls or resilient.demotions:
        problems.append("tbls ladder was used")
    if coalescer.host_fallback_flushes or coalescer.pack_fallbacks:
        problems.append("coalescer fell back to host code")
    if coalescer._degraded:
        problems.append("coalescer degraded its plane")
    if list(tpu._degrade_rungs) != ["msm-off", "fp2-fusion-off"]:
        problems.append("TPUImpl burned a degrade rung")
    pub = warm.get("pubkey", {})
    if pub.get("python") or not pub.get("device"):
        problems.append(f"warm-up did not run on the device: {pub}")
    for family in report["wave_families"]:
        if getattr(plane, f"_{family}")._cache_size() != 1:
            problems.append(f"{family} ran at a second, uncompiled bucket")
    if not coalescer.flushes:
        problems.append("no flush reached the plane")
    if problems:
        raise RuntimeError("; ".join(problems))


# ---------------------------------------------------------------------------
# --chips 4: the sharded plane against the one-device plane
# ---------------------------------------------------------------------------


def four_chip(jax, bucket: int = 256, t: int = 4) -> None:
    """`_verify_rlc` and `_step_rlc` at one bucket on a four-device mesh
    and on a one-device mesh, same inputs: equal verdicts and aggregate
    bytes, and every array of the four-device run spread over four
    devices."""
    import random

    from charon_tpu.crypto.g1g2 import g1_from_bytes, g2_from_bytes, g2_to_bytes
    from charon_tpu.ops import curve as C
    from charon_tpu.parallel import SlotCryptoPlane, make_mesh
    from charon_tpu.tbls.native_impl import NativeImpl
    from charon_tpu.tbls.tpu_impl import _cached_msg_point

    native = NativeImpl()
    rng = random.Random(SEED)
    # `bucket` verify lanes and bucket/4 recombine rows, real signatures
    v = bucket // 4
    root = hashlib.sha256(b"chip-smoke/four").digest()
    msg_pt = _cached_msg_point(root)
    rows = []
    for i in range(v):
        secret = rng.randrange(1, 1 << 250).to_bytes(32, "big")
        shares = native.threshold_split(secret, 7, t)
        idx = sorted(rng.sample(sorted(shares), t))
        rows.append(
            (
                # the native signer's own outputs: no subgroup re-check
                [g1_from_bytes(native.secret_to_public_key(shares[j]), False) for j in idx],
                [g2_from_bytes(native.sign(shares[j], root), False) for j in idx],
                g1_from_bytes(native.secret_to_public_key(secret), False),
                idx,
                native.sign(secret, root),
            )
        )
    step_in = (
        [r[0] for r in rows], [msg_pt] * v, [r[1] for r in rows],
        [r[2] for r in rows], [r[3] for r in rows],
    )
    pks = [p for r in rows for p in r[0]]
    sigs = [s for r in rows for s in r[1]]
    verify_in = (pks, [msg_pt] * len(pks), sigs)

    devices_of = lambda tree: sorted(
        {len(sh.device_set) for sh in jax.tree_util.tree_leaves(tree)}
    )
    planes, programs = {}, []
    for ndev in (4, 1):
        plane = planes[ndev] = SlotCryptoPlane(
            make_mesh(jax.devices()[:ndev]), t=t
        )
        seeded = random.Random(SEED)
        programs += [
            (ndev, "verify_rlc", plane._verify_rlc,
             (*plane.pack_verify_inputs(*verify_in),
              plane.make_lane_rand(len(pks), rng=seeded))),
            (ndev, "step_rlc", plane._step_rlc,
             (*plane.pack_inputs(*step_in), plane.make_rand(v, rng=seeded))),
        ]

    # the plane builds its inputs on the default device and lets jit
    # reshard them: lower each plane's own jitted program once (four
    # threads — XLA compiles outside the GIL) and ask the executable
    # where its arguments and results live
    compiled = {}

    def lower_and_compile(ndev, name, prog, args):
        compiled[ndev, name] = prog.lower(*args).compile()

    first_calls_in_parallel(
        {"programs": []},
        [
            (f"mesh/{name}@{ndev}dev", bucket,
             functools.partial(lower_and_compile, ndev, name, prog, args))
            for ndev, name, prog, args in programs
        ],
    )

    results = {}
    for ndev in (4, 1):
        entry = {"mesh_devices": ndev, "bucket": bucket, "recombine_rows": v}
        for pdev, name, _prog, args in programs:
            if pdev != ndev:
                continue
            exe = compiled[ndev, name]
            jax.block_until_ready(exe(*args))
            t0 = time.monotonic()
            out = jax.block_until_ready(exe(*args))
            entry[f"{name}_step_seconds"] = round(time.monotonic() - t0, 4)
            entry[f"{name}_input_devices"] = devices_of(exe.input_shardings[0])
            entry[f"{name}_output_devices"] = devices_of(
                jax.tree_util.tree_map(lambda a: a.sharding, out)
            )
            results[ndev, name] = out
        emit(phase="four_chip", **entry)
        spread = {
            val for key, vals in entry.items() if key.endswith("_devices")
            for val in vals
        }
        if spread != {ndev}:
            raise RuntimeError(
                f"arrays are not all spread over the {ndev}-device mesh: {entry}"
            )
        results[ndev, "sig_bytes"] = [
            g2_to_bytes(p)
            for p in C.g2_unpack(
                planes[ndev].ctx, results[ndev, "step_rlc"][0]
            )[:v]
        ]

    ok4, ok1 = bool(results[4, "verify_rlc"]), bool(results[1, "verify_rlc"])
    all4, all1 = bool(results[4, "step_rlc"][1]), bool(results[1, "step_rlc"][1])
    ref = [r[4] for r in rows]
    emit(phase="four_chip_compare", verify_ok=[ok4, ok1], step_ok=[all4, all1],
         bytes_equal_across_meshes=results[4, "sig_bytes"] == results[1, "sig_bytes"],
         bytes_equal_native=results[4, "sig_bytes"] == ref)
    if not (ok4 and ok1 and all4 and all1):
        raise RuntimeError("a valid batch did not verify")
    if results[4, "sig_bytes"] != results[1, "sig_bytes"] or results[4, "sig_bytes"] != ref:
        raise RuntimeError("aggregate bytes differ")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    dev = device_info(jax)
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        # no CPU mode: a measurement path that finds no chip fails
        print(json.dumps({"ok": False, "device": dev,
                          "error": f"need {args.chips} TPU chip(s)"}))
        return 1

    from charon_tpu import jaxcache
    from charon_tpu.core import autotune
    from charon_tpu.ops import limb

    cache_dir = jaxcache.configure(jax, cpu=False)
    jax.monitoring.register_event_listener(_on_jax_event)
    # cut (PERF.md "what was cut"): the Straus-MSM routing of the
    # recombine step is pinned off through the existing deploy pin —
    # its step program alone takes longer to compile for a v5e than
    # this script may run. resolve("off") at node start honours the
    # same pin, so every program of the run is traced under one routing.
    os.environ["CHARON_MSM"] = "0"
    autotune.apply_env()
    emit(phase="start", device=dev, jax=jax.__version__,
         jaxlib=__import__("jaxlib").__version__,
         libtpu=_libtpu_version(), compile_cache_dir=cache_dir,
         routing=routing(limb, limb.default_fp_ctx()))
    t0 = time.monotonic()
    if args.chips == 4:
        four_chip(jax)
        dev["count"] = 4
    else:
        report = asyncio.run(serve(Sizes()))
        check_device_path(report, jax)
    emit(phase="done", seconds=round(time.monotonic() - t0, 1),
         compile_cache=jaxcache.cache_stats())
    print(json.dumps({"ok": True, "device": dev}))
    return 0


def _libtpu_version() -> str | None:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # informational only
        return None


if __name__ == "__main__":
    sys.exit(main())
