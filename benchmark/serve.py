"""The served path of one real node, driven for one window.

Copied from chip_smoke.py (PR 22) and made into a window: the node is
`app.run.build_node(Config(**config["node"]))`; its VC submits partials over
the ValidatorAPI HTTP router, its n-1 peers (host-only: real P2PNode, QBFT,
scheduler; the harness's signer) send theirs through ParSigEx over TCP, and a
duty is done when the node's beacon holds the broadcast aggregate. Every
duty is timed from the instant its trigger was DUE on the slot clock.

What belongs to ONE kind of duty — who holds it when, what the beacon
answers, what is signed and submitted where, and who STARTS it — is the
kind's module (duties/<kind>.py, found by the name in the mix); what is here
serves whatever kinds the plan carries. A kind the cluster decides is driven
by the node's scheduler (its VC) and each peer's QBFT decision; a kind that
validator clients start (`STARTS = "vc"`) by the slot clock, `started_rounds`:
README.md, "Adding things"."""

from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import functools
import socket
import tempfile
import threading
import time
from pathlib import Path

from benchmark import cluster as clusterlib, reference, signer
from benchmark.traffic import Plan


@dataclasses.dataclass
class DutyRecord:
    kind: str  # the duty module's NAME
    slot: int
    vidx: int
    pubkey: str
    due: float  # wall clock: slot start + the kind's place in the slot
    root: bytes | None = None  # the signing root the VC signed (the program's SSZ)
    data: tuple | None = None  # raw fields of the object the beacon received
    done: float | None = None  # wall clock: the node's beacon got it
    signature: bytes | None = None
    broadcasts: int = 0


@dataclasses.dataclass
class RunData:
    """Everything a reader may read; times are wall clock seconds."""

    setup_s: float = 0.0
    window: tuple[float, float] = (0.0, 0.0)
    slot_duration: float = 12.0
    slots: list[int] = dataclasses.field(default_factory=list)
    # the mix's kinds as a node span's `duty` spells them ("7/attester")
    duty_types: tuple[str, ...] = ()
    duties: list[DutyRecord] = dataclasses.field(default_factory=list)
    flushes: list = dataclasses.field(default_factory=list)  # (done, FlushStats)
    programs: list = dataclasses.field(default_factory=list)  # (family, s, lanes, end)
    spans: list = dataclasses.field(default_factory=list)  # (name, start, end)
    gave_up: float = 0.0  # when a missing duty stopped being waited for
    trace: object | None = None  # tracered.TraceSummary
    traced_slot: int | None = None

    def in_window(self, ts: float) -> bool:
        return self.window[0] <= ts < self.window[1] + self.slot_duration

    def waves(self) -> list[dict]:
        out = []
        for slot in self.slots:
            ds = [d for d in self.duties if d.slot == slot]
            done = [d.done for d in ds if d.done is not None]
            out.append({
                "slot": slot, "duties": len(ds), "due": min(d.due for d in ds) if ds else 0.0,
                "last_done": max(done) if len(done) == len(ds) and ds else None,
            })
        return out


def free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class Gate:
    """Slots the window serves: [first, first + count). Peers and the VC
    stay silent outside it (the node boots, warms and loads first).
    `opened` is set once the window's slots are known."""

    def __init__(self) -> None:
        self.first: int | None = None
        self.count = 0
        self.opened = asyncio.Event()

    def serve(self, first: int, count: int) -> None:
        self.first, self.count = first, count
        self.opened.set()

    def open(self, slot: int) -> bool:
        return self.first is not None and self.first <= slot < self.first + self.count

    @property
    def last(self) -> int:
        return (self.first or 0) + self.count - 1


class SlotMemo:
    """What every operator's beacon and signer would compute alike, once:
    the six peers stand for six other machines, and in one interpreter
    their repeated SSZ hashing would sit on the node's own event loop."""

    def __init__(self) -> None:
        self._made: dict = {}

    def once(self, key, make):
        if key not in self._made:
            self._made[key] = make()
        return self._made[key]


@dataclasses.dataclass
class Scene:
    """What every actor of a run shares, and a kind's functions are given."""

    plan: Plan
    cluster: object
    memo: SlotMemo = dataclasses.field(default_factory=SlotMemo)
    genesis: float = 0.0  # the run's slot clock: slot s starts at genesis + s * slot_duration

    @functools.cached_property
    def fork(self):
        """Once a run: the lock derives it anew on every call (milliseconds
        at 1,000 validators), and a signer asks for it on every signing root
        it has not met — 31-32 a wave, inside the VC's round."""
        return self.cluster.lock.fork_info()


def kinds_by_type(plan: Plan) -> dict:
    from charon_tpu.core.types import DutyType

    return {DutyType[kind.DUTY_TYPE]: kind for kind in plan.kinds}


def vc_started(kind) -> bool:
    """Who starts a duty of this kind: absent or "decided", the node's
    scheduler triggers the VC's round and a peer sends when its QBFT
    decides; "vc", the duty exists because validator clients send it — no
    scheduler emits it and no consensus runs on it."""
    starts = getattr(kind, "STARTS", "decided")
    if starts not in ("decided", "vc"):
        raise ValueError(f"duty kind {kind.NAME}: STARTS {starts!r}")
    return starts == "vc"


def started_rounds(scene: Scene, gate: Gate, fire) -> list:
    """One task a kind that validator clients start: once the window is
    known, `fire(kind, slot)` as a task of its own at each of its slots in
    which the kind has members, the instant the kind's request is DUE on
    the slot clock (slot start + OFFSET x slot duration)."""
    plan = scene.plan

    async def rounds(kind):
        await gate.opened.wait()
        fired = set()
        for slot in range(gate.first, gate.first + gate.count):
            if not kind.members(plan, slot):
                continue
            due = scene.genesis + (slot + kind.OFFSET) * plan.slot_duration
            await asyncio.sleep(max(0.0, due - time.time()))
            task = asyncio.create_task(fire(kind, slot))
            fired.add(task)
            task.add_done_callback(fired.discard)
        await asyncio.gather(*fired)

    return [asyncio.create_task(rounds(kind)) for kind in plan.kinds if vc_started(kind)]


# the BeaconMock's own schedules (every validator, every slot): none of them
# unless a kind of the mix answers it
SCHEDULES = ("attester_duties", "proposer_duties", "sync_duties")


def make_beacon(scene: Scene, genesis: float):
    """The repo's BeaconMock with the duties of the plan's kinds and no
    others: each kind's module gives the answers to the scheduler's and
    the fetcher's queries for it. Block roots come from the seed, the
    same on every operator's beacon."""
    from charon_tpu.testutil.beaconmock import BeaconMock

    async def none(self, epoch, vals):
        return []

    methods = dict.fromkeys(SCHEDULES, none)
    for kind in scene.plan.kinds:
        methods.update(kind.beacon(scene))
    return type("HarnessBeacon", (BeaconMock,), methods)(
        validators=dict(scene.cluster.validators),
        genesis_time=genesis,
        slot_duration=scene.plan.slot_duration,
        slots_per_epoch=scene.plan.slots_per_epoch,
    )


class HostPeer:
    """One of the other operators: a real P2PNode with a real QBFT
    participant, scheduler and fetcher (so the cluster decides every
    duty), and instead of a VC + ValidatorAPI + SigAgg the harness's signer
    that sends this operator's partials through ParSigEx once the duty is
    decided (after the plan's jitter) — or, for a kind that validator
    clients start, once its request is due on the slot clock, with no
    decision behind it. It verifies and aggregates nothing: tbls is
    process-global and belongs to the chip-backed node."""

    def __init__(self, scene: Scene, index, ports, genesis, gate, spans):
        self.scene, self.plan, self.cluster, self.index = scene, scene.plan, scene.cluster, index
        self.ports, self.genesis, self.gate = ports, genesis, gate
        self.spans, self.kinds = spans, kinds_by_type(scene.plan)
        self.sent_sets = 0
        self.forged_sets = 0
        self._sends: set = set()

    async def start(self) -> None:
        from charon_tpu.core.consensus_qbft import QBFTConsensus
        from charon_tpu.core.deadline import SlotClock
        from charon_tpu.core.fetcher import Fetcher
        from charon_tpu.core.parsigex import DutyGater, ParSigEx
        from charon_tpu.core.scheduler import Scheduler
        from charon_tpu.eth2util import enr
        from charon_tpu.p2p.adapters import TcpParSigTransport, TcpQbftNet
        from charon_tpu.p2p.transport import P2PNode, PeerSpec

        plan, lock = self.plan, self.cluster.lock
        op_pubkeys = [enr.pubkey_from_string(op.enr) for op in lock.definition.operators]
        specs = [
            PeerSpec(index=i, pubkey=pk, host="127.0.0.1", port=self.ports[i])
            for i, pk in enumerate(op_pubkeys)
        ]
        self.p2p = P2PNode(
            self.index, self.cluster.k1_keys[self.index], specs, lock.lock_hash()
        )
        await self.p2p.start()
        clock = SlotClock(self.genesis, plan.slot_duration)
        gater = DutyGater(clock, slots_per_epoch=plan.slots_per_epoch)
        self.qbft = QBFTConsensus(
            TcpQbftNet(self.p2p),
            plan.operators,
            privkey=self.cluster.k1_keys[self.index],
            pubkeys=op_pubkeys,
            gater=gater,
        )
        self.parsigex = ParSigEx(self.index + 1, TcpParSigTransport(self.p2p), gater=gater)
        beacon = make_beacon(self.scene, self.genesis)
        self._fetcher = Fetcher(beacon)
        self._fetcher.register_consensus(self.qbft.propose)
        self.scheduler = Scheduler(
            beacon, clock, self.cluster.validators, slots_per_epoch=plan.slots_per_epoch
        )
        self.scheduler.subscribe_duties(self._fetch)
        self.qbft.subscribe(self._decided)
        self._task = asyncio.create_task(self.scheduler.run())
        self._rounds = started_rounds(self.scene, self.gate, self._started)

    async def _fetch(self, duty, defs) -> None:
        if duty.type in self.kinds:
            await self._fetcher.fetch(duty, defs)

    async def _decided(self, duty, unsigned_set) -> None:
        kind = self.kinds.get(duty.type)
        if kind is None or vc_started(kind) or not self.gate.open(duty.slot):
            return
        self.spans.append(("qbft_decided", time.time(), time.time()))
        self._speak(duty, unsigned_set)

    async def _started(self, kind, slot: int) -> None:
        """This operator's VC has sent its request: the same set every
        operator's VC signs in that slot, with no decision behind it."""
        self._speak(kind.duty(self.plan, slot), kind.unsigned(self.scene, slot))

    def _speak(self, duty, unsigned_set) -> None:
        share_idx = self.index + 1
        if share_idx in self.plan.silent:
            return
        task = asyncio.create_task(self._send(duty, unsigned_set, share_idx))
        self._sends.add(task)
        task.add_done_callback(self._sends.discard)

    async def _send(self, duty, unsigned_set, share_idx) -> None:
        from charon_tpu.core.eth2data import ParSignedData

        plan = self.plan
        # jitter and fault are drawn for the slot of SENDING: duty.slot for
        # every kind there is (a kind whose duties travel under another slot
        # than the one they are sent in has to say both)
        await asyncio.sleep(plan.jitter(share_idx, duty.slot))
        forge = plan.forged(duty.slot, share_idx, self.gate.last, self.kinds[duty.type].NAME)
        # on a thread: this operator is another machine, and its
        # signatures may not hold the node's event loop
        signed = await asyncio.to_thread(
            self.kinds[duty.type].sign, self.scene, self.cluster.share_keys[self.index],
            duty, unsigned_set)
        signed_set = {}
        for n, (pk, obj) in enumerate(signed.items()):
            sig = obj.signature
            if forge and n < plan.fault.partials:
                if plan.fault.kind == "flip_byte":
                    sig = sig[:10] + bytes([sig[10] ^ 0x40]) + sig[11:]
                else:  # wrong_key: well formed, signed by another secret
                    sig = signer.sign(
                        reference.seeded_scalar("forger", plan.seed, n).to_bytes(32, "big"),
                        b"forged" + bytes(26),
                    )
            signed_set[pk] = ParSignedData(obj.with_signature(sig), share_idx)
        await self.parsigex.broadcast(duty, signed_set)
        self.sent_sets += 1
        self.forged_sets += 1 if forge else 0

    async def stop(self) -> None:
        self.scheduler.stop()
        self._task.cancel()
        for t in [*self._rounds, *self._sends]:
            t.cancel()
        await self.p2p.stop()


# ---------------------------------------------------------------------------


class Server:
    """One run's node, peers and hooks; `run.py` drives its phases."""

    def __init__(self, cell, plan: Plan, seed: int, wd, cache_log, events, allowed,
                 require_plane: bool = True):
        self.cell, self.plan, self.seed, self.wd = cell, plan, seed, wd
        self.cache_log, self.events = cache_log, events
        self.allowed = allowed  # {"family@bucket"}
        self.require_plane = require_plane
        self.run = RunData(slot_duration=plan.slot_duration,
                           duty_types=tuple(kind.DUTY_TYPE.lower() for kind in plan.kinds))
        self.gate = Gate()
        self.in_window = False
        self.warm_stats: list[dict] = []
        # callable(family, wall clock) | None: called on the plane's dispatch
        # thread the instant a compiled program has returned, before its
        # caller has the result (run.py starts the end of the trace there)
        self.on_program_end = None
        self._records: dict[tuple[str, int, int], DutyRecord] = {}
        self._tmp = None
        self.client = None
        self.life = None
        self.stop = asyncio.Event()
        self.peers: list[HostPeer] = []
        self._rounds: list = []

    # -- phase: cluster -----------------------------------------------------

    def make_cluster(self) -> None:
        cfg = self.cell.config
        self.cluster = clusterlib.make_cluster(
            self.seed, cfg["operators"], cfg["threshold"], cfg["validators"]
        )
        self._tmp = tempfile.TemporaryDirectory(prefix="bench_node_")
        self.node_dir = Path(self._tmp.name) / "node0"
        clusterlib.write_node_dir(self.cluster, 0, self.node_dir, int(cfg["keystore_kdf_c"]))

    # -- phase: node --------------------------------------------------------

    async def build_node(self) -> None:
        from charon_tpu import tbls
        from charon_tpu.app.run import Config, build_node

        plan, cfg = self.plan, self.cell.config
        self.ports = free_ports(plan.operators)
        self.genesis = time.time()
        self.scene = Scene(plan, self.cluster, genesis=self.genesis)
        self.kinds = kinds_by_type(plan)
        self.beacon = make_beacon(self.scene, self.genesis)
        self._hook_beacon()
        self.node = await build_node(
            Config(
                data_dir=str(self.node_dir),
                node_index=0,
                p2p_port=self.ports[0],
                peer_addrs=[("127.0.0.1", p) for p in self.ports],
                beacon_nodes=[self.beacon],
                slot_duration=plan.slot_duration,
                slots_per_epoch=plan.slots_per_epoch,
                genesis_time=self.genesis,
                **cfg["node"],
            )
        )
        self.tbls = tbls.get_implementation()
        self.coalescer = self.node.crypto_plane
        if self.coalescer is None and self.require_plane:
            raise RuntimeError("build_node installed no crypto plane")
        self.node.scheduler.subscribe_duties(self._vc_on_duty)
        self._rounds = started_rounds(self.scene, self.gate, self._vc_started)

    def record(self, kind: str, slot: int, vidx: int) -> DutyRecord | None:
        return self._records.get((kind, slot, vidx))

    def _hook_beacon(self) -> None:
        """Stamp every aggregate the node broadcasts, where it lands: each
        kind names the beacon's `submit_*` it ends at and reads the
        submitted object back into (slot, validator, signature, raw fields)."""
        for kind in self.plan.kinds:
            setattr(self.beacon, kind.SUBMIT,
                    self._stamped(kind, getattr(self.beacon, kind.SUBMIT)))

    def _stamped(self, kind, inner):
        async def submit(*args):
            now = time.time()
            found = kind.submitted(self.scene, *args)
            if found is not None:
                slot, vidx, signature, data = found
                rec = self.record(kind.NAME, slot, vidx)
                if rec is not None:
                    rec.broadcasts += 1
                    if rec.done is None:
                        rec.done, rec.signature, rec.data = now, signature, data
            await inner(*args)

        return submit

    async def _vc_on_duty(self, duty, defs) -> None:
        """This node's validator client: HTTP against the ValidatorAPI,
        each kind's own round."""
        kind = self.kinds.get(duty.type)
        if kind is None or vc_started(kind) or not self.gate.open(duty.slot):
            return
        self.run.spans += await kind.vc_round(self, duty, defs)

    async def _vc_started(self, kind, slot: int) -> None:
        """The same client, for a kind no scheduler emits: its round at the
        instant its request is due, `defs` the objects it signs. A round
        the node refuses is noted, and its duties are then missing."""
        try:
            self.run.spans += await kind.vc_round(
                self, kind.duty(self.plan, slot), kind.unsigned(self.scene, slot))
        except Exception as e:  # noqa: BLE001 — the VC is another process: the run goes on
            self.wd.note(f"the VC's {kind.NAME} round of slot {slot} failed: "
                         f"{type(e).__name__}: {e}")

    # -- phase: programs ----------------------------------------------------

    def hook_plane(self) -> None:
        """Observers in front of the node's own hooks: warm-up stats,
        per-flush stats, per-program spans — and the gate on programs:
        a dispatch of any family@bucket outside the configuration's
        list ends the run at once."""
        coalescer, plane = self.coalescer, self.coalescer.plane
        inner_warm, inner_stats = coalescer.warmup_hook, coalescer.stats_hook
        inner_prog = plane.on_program
        run = self.run

        def warm_hook(stats):
            self.warm_stats.append(stats)
            if inner_warm is not None:
                inner_warm(stats)

        def stats_hook(s):
            run.flushes.append((time.time(), s))
            if inner_stats is not None:
                inner_stats(s)

        def program_hook(family, seconds, lanes):
            name = f"{family.split('/', 1)[-1]}@{plane.bucket_lanes(lanes)}"
            short, now = family.split("/", 1)[-1], time.time()
            run.programs.append((short, seconds, lanes, now))
            if self.on_program_end is not None:
                self.on_program_end(short, now)
            if name not in self.allowed:
                self.wd.fail(
                    f"a flush left the compiled set: {name} ({lanes} lanes) is not "
                    f"in the configuration's programs {sorted(self.allowed)}",
                    family=family, bucket=plane.bucket_lanes(lanes), lanes=lanes)
            if inner_prog is not None:
                inner_prog(family, seconds, lanes)

        coalescer.warmup_hook = warm_hook
        coalescer.stats_hook = stats_hook
        plane.on_program = program_hook

    def load_programs(self, warm_expected: bool) -> list[dict]:
        """First dispatch of every wave program on the configuration's
        list through the plane's own prewarm entries, traced one after
        the other in the list's order. Blocking: call it in a thread."""
        cfg = self.cell.config
        plane = self.coalescer.plane
        want = []  # (family, bucket): a family may be listed at several buckets
        for item in cfg["programs"]:
            family, bucket = item.split("@")
            if family != "g1dec":  # compiled by the node's own warm-up
                want.append((family, int(bucket)))
        dec = self.coalescer._decode_rung() == "device"
        verify = sorted({b for f, b in want if f.startswith("verify")})
        step = sorted({b for f, b in want if f.startswith("step")})
        entries = plane.prewarm_programs(
            verify_lanes=tuple(verify), recombine_lanes=tuple(step), decompress=dec
        )
        todo = [(family, bucket, fn) for _k, family, bucket, fn in entries
                if (family, bucket) in want]
        if len(todo) != len(want):
            raise RuntimeError(
                f"prewarm_programs offers {[(f, b) for f, b, _ in todo]}, "
                f"the configuration lists {want}")
        log: list[dict] = []
        # Trace ORDER is part of a program's cache key: the Pallas kernels
        # are lowered once, by whichever program meets them first, and carry
        # that caller's source locations into every module that uses them.
        # Two programs traced on two free-running threads gave two different
        # pairs of keys in two processes (my chip run, PR 25). So program
        # i+1 starts tracing only when program i is lowered; its XLA
        # compile or cache load then runs outside the GIL, beside the
        # next trace.
        looked_up = [threading.Event() for _ in todo]
        thread_of: dict[int, int] = {}

        def on_lowered():
            i = thread_of.get(threading.get_ident())
            if i is not None:
                looked_up[i].set()

        self.events.on_plane_lowered = on_lowered

        def first_call(i, family, bucket, fn):
            if i:
                looked_up[i - 1].wait()
            me = threading.get_ident()
            thread_of[me] = i
            hits0 = self.events.hits.get(me, 0)
            t0 = time.monotonic()
            self.wd.note(f"program {family}@{bucket}: trace + "
                         f"{'load' if warm_expected else 'compile'} start")
            try:
                fn()
            finally:
                looked_up[i].set()
            entry = {
                "program": f"{family}@{bucket}",
                "seconds": round(time.monotonic() - t0, 2),
                "cache": "hit" if self.events.hits.get(me, 0) > hits0 else "miss",
            }
            self.wd.note(f"program {family}@{bucket}: ready after "
                         f"{entry['seconds']} s ({entry['cache']})")
            log.append(entry)

        todo.sort(key=lambda job: cfg["programs"].index(f"{job[0]}@{job[1]}"))
        with concurrent.futures.ThreadPoolExecutor(len(todo)) as pool:
            futures = [pool.submit(first_call, i, *job) for i, job in enumerate(todo)]
            for f in futures:
                f.result()
        self.events.on_plane_lowered = None
        return log

    # -- phase: peers, warm-up ----------------------------------------------

    async def start_peers(self) -> None:
        from charon_tpu.testutil.vapiclient import HttpVapiClient

        self.peers = [
            HostPeer(self.scene, i, self.ports, self.genesis, self.gate, self.run.spans)
            for i in range(1, self.plan.operators)
        ]
        for p in self.peers:
            await p.start()
        vapi_port = await self.node.vapi_router.start("127.0.0.1", 0)
        self.client = HttpVapiClient(f"http://127.0.0.1:{vapi_port}", self.cluster.validators)
        self.life = asyncio.create_task(self.node.life.run(self.stop))

    async def await_warmup(self) -> dict:
        """The lifecycle's own start-up warm-up: the whole key table
        through the device g1dec program."""
        while not self.warm_stats:
            await asyncio.sleep(0.1)
            if self.life.done():
                self.life.result()
                raise RuntimeError("the node's lifecycle ended during warm-up")
        return self.warm_stats[0]

    # -- phase: align, window -----------------------------------------------

    def open_window(self, slots: int, lead: float = 0.75) -> float:
        """Gate the next slot boundary at least `lead` seconds away and
        the `slots` slots from it; returns the boundary (wall clock)."""
        clock = self.beacon.clock()
        first = clock.slot_at(time.time() + lead) + 1
        self.gate.serve(first, slots)
        start = clock.slot_start(first)
        run, plan = self.run, self.plan
        run.slots = list(range(first, first + slots))
        run.window = (start, start + slots * plan.slot_duration)
        for slot in run.slots:
            for kind in plan.kinds:
                due = clock.slot_start(slot) + kind.OFFSET * plan.slot_duration
                for vidx in kind.members(plan, slot):
                    rec = DutyRecord(kind.NAME, slot, vidx, self.cluster.pubkeys[vidx], due)
                    self._records[(kind.NAME, slot, vidx)] = rec
                    run.duties.append(rec)
        return start

    def expected_forged_sets(self) -> int:
        return sum(
            1 for slot in self.run.slots for kind in self.plan.kinds
            for idx in range(2, self.plan.operators + 1)
            if self.plan.forged(slot, idx, self.gate.last, kind.NAME)
            and idx not in self.plan.silent and kind.members(self.plan, slot)
        )

    # -- phase: teardown ----------------------------------------------------

    async def teardown(self) -> list[str]:
        """Bounded: each part gets its seconds, a part that hangs is
        noted and left (the process exits by os._exit after the last
        line)."""
        late = []
        self.stop.set()
        for t in self._rounds:
            t.cancel()

        async def bounded(name, coro, seconds):
            try:
                await asyncio.wait_for(coro, timeout=seconds)
            except asyncio.TimeoutError:
                late.append(name)
            except Exception as e:  # noqa: BLE001 — teardown reports, never raises
                late.append(f"{name}: {type(e).__name__}: {e}")

        if self.client is not None:
            await bounded("http client", self.client.close(), 3)
        for p in self.peers:
            await bounded(f"peer {p.index}", p.stop(), 3)
        if self.life is not None:
            await bounded("lifecycle", self.life, 10)
        if self._tmp is not None:
            await bounded("temp dir", asyncio.to_thread(self._tmp.cleanup), 5)
        return late


def device_counters(server: Server) -> dict:
    """The smoke's check_device_path, as numbers: every ladder on the
    path still on its top rung, no lane served by host code."""
    from charon_tpu.ops import limb
    from charon_tpu.tbls.tpu_impl import TPUImpl

    coalescer = server.coalescer
    plane = coalescer.plane
    resilient = server.tbls
    tpu = resilient.impls[0]
    warm = server.warm_stats[0] if server.warm_stats else {}
    pub = warm.get("pubkey", {}) or {}
    events = {
        "top_rung_not_tpu": 0 if isinstance(tpu, TPUImpl) else 1,
        "not_u32_limbs": 0 if (plane.ctx is limb.FP32 and plane.fr_ctx is limb.FR32) else 1,
        "pallas_inactive": 0 if limb._pallas_active(plane.ctx) else 1,
        "decode_rung_not_device": 0 if coalescer._decode_rung() == "device" else 1,
        "resilient_fallback_calls": int(resilient.fallback_calls),
        "resilient_demotions": len(resilient.demotions),
        "host_fallback_flushes": int(coalescer.host_fallback_flushes),
        "pack_fallbacks": int(coalescer.pack_fallbacks),
        "coalescer_degraded": 1 if coalescer._degraded else 0,
        "degrade_rungs_burned": 0 if list(getattr(tpu, "_degrade_rungs", []))
        == ["msm-off", "fp2-fusion-off"] else 1,
        "warmup_python_lanes": int(pub.get("python") or 0),
        "warmup_not_on_device": 0 if pub.get("device") else 1,
        "fallback_flushes": sum(1 for _ts, s in server.run.flushes if s.fallback),
    }
    info = {
        "ctx": plane.ctx.name,
        "flushes": coalescer.flushes,
        "lanes_flushed": coalescer.lanes_flushed,
        "compiled_programs": plane.jit_cache_size(),
        "warmup": {k: warm.get(k) for k in ("pubkey", "message", "seconds") if k in warm},
    }
    return {"events": events, "info": info}
