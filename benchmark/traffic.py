"""The one general traffic generator: a mix is a data file
(mixes/<name>.json), this turns it and --seed into a plan.

The plan fixes, before anything boots: which validators attest in which
slot of the epoch (a seeded permutation; every validator once an epoch,
spread evenly, so a slot has floor(v/spe) or ceil(v/spe) duties for every
seed), the per-peer send jitter, the fault (none / flip_byte / wrong_key)
and the silent operators — and from those every (family, bucket) a whole
wave's flushes can land on, which must be the configuration's programs."""

from __future__ import annotations

import dataclasses
import hashlib
import random


class TrafficError(ValueError):
    pass


def bucket_lanes(n: int) -> int:
    """The program's one-device bucket ladder (ops/blsops.bucket_lanes):
    next power of two, minimum 4."""
    return max(4, 1 << max(0, n - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class Fault:
    kind: str  # none | flip_byte | wrong_key
    operator: int  # 1-based share index of the forging peer (0: none)
    slots: str  # last | all
    partials: int  # forged partials per forged set


@dataclasses.dataclass(frozen=True)
class Plan:
    seed: int
    operators: int
    threshold: int
    validators: int
    slots_per_epoch: int
    slot_duration: float
    duties: tuple[str, ...]
    jitter_s: float
    fault: Fault
    silent: tuple[int, ...]  # 1-based share indices that never send
    rank: tuple[int, ...]  # validator index -> rank in the seeded order

    def members(self, slot: int) -> list[int]:
        """Validator indices attesting in `slot`, in committee order."""
        pos = slot % self.slots_per_epoch
        chosen = [(r, v) for v, r in enumerate(self.rank)
                  if r % self.slots_per_epoch == pos]
        return [v for _r, v in sorted(chosen)]

    def duties_in(self, position: int) -> int:
        return sum(1 for r in self.rank if r % self.slots_per_epoch == position)

    def senders(self) -> int:
        """Partial sets a wave carries: the peers that speak + the VC."""
        return self.operators - len(self.silent)

    def jitter(self, share_idx: int, slot: int) -> float:
        digest = hashlib.sha256(
            f"jitter/{self.seed}/{share_idx}/{slot}".encode()).digest()
        return self.jitter_s * int.from_bytes(digest[:8], "big") / 2**64

    def block_root(self, *parts) -> bytes:
        return hashlib.sha256(
            ("att/%d/" % self.seed + "/".join(str(p) for p in parts)).encode()
        ).digest()

    def attestation_fields(self, slot: int, committee_index: int) -> tuple:
        """The raw fields of the slot's AttestationData, the same on every
        operator's beacon: (slot, index, beacon block root, source epoch,
        source root, target epoch, target root). The program's objects
        and the plain reference's signing root are both made from these."""
        epoch = slot // self.slots_per_epoch
        return (slot, committee_index, self.block_root("block", slot),
                max(0, epoch - 1), self.block_root("cp", epoch - 1),
                epoch, self.block_root("cp", epoch))

    def forged(self, slot: int, share_idx: int, last_slot: int) -> bool:
        if self.fault.kind == "none" or share_idx != self.fault.operator:
            return False
        return self.fault.slots == "all" or slot == last_slot

    def flush_shapes(self) -> set[str]:
        """Every `family@bucket` a WHOLE wave's flushes land on: one
        verify flush of every speaking operator's set, one recombine
        flush of the slot's duties (decode on the device)."""
        shapes = set()
        for pos in range(self.slots_per_epoch):
            d = self.duties_in(pos)
            if d == 0:
                continue
            shapes.add(f"verify_rlc_dec@{bucket_lanes(d * self.senders())}")
            shapes.add(f"step_rlc_dec@{bucket_lanes(d)}")
            if self.fault.kind == "wrong_key":
                # a well-formed forgery fails the RLC tier: attribution
                shapes.add(f"verify_dec@{bucket_lanes(d * self.senders())}")
        return shapes


def make_plan(config: dict, traffic: dict, seed: int) -> Plan:
    duties = tuple(traffic.get("duties", ()))
    if duties != ("attester",):
        raise TrafficError(
            f"traffic {traffic.get('name')!r}: duties {list(duties)} — only "
            "the attester wave is generated yet")
    n, t = int(config["operators"]), int(config["threshold"])
    v, spe = int(config["validators"]), int(config["slots_per_epoch"])
    f = traffic.get("fault") or {"kind": "none"}
    kind = f.get("kind", "none")
    if kind not in ("none", "flip_byte", "wrong_key"):
        raise TrafficError(f"fault kind {kind!r}")
    op = f.get("operator", "last")
    operator = 0 if kind == "none" else (n if op == "last" else int(op))
    if kind != "none" and not 2 <= operator <= n:
        raise TrafficError(f"forging operator {operator}: peers are 2..{n}")
    silent = tuple(sorted(int(i) for i in traffic.get("silent_operators", ())))
    if any(not 2 <= i <= n for i in silent) or operator in silent:
        raise TrafficError(f"silent operators {silent}: peers are 2..{n}")
    forgers = 1 if kind != "none" else 0
    if n - len(silent) - forgers < t:
        raise TrafficError("fewer than t honest operators speak: no duty completes")
    order = list(range(v))
    random.Random(f"order/{seed}").shuffle(order)
    rank = [0] * v
    for r, vidx in enumerate(order):
        rank[vidx] = r
    return Plan(
        seed=seed, operators=n, threshold=t, validators=v, slots_per_epoch=spe,
        slot_duration=float(config["slot_duration_s"]), duties=duties,
        jitter_s=float(traffic.get("send_jitter_ms", 0)) / 1000.0,
        fault=Fault(kind, operator, f.get("slots", "last"), int(f.get("partials", 1))),
        silent=silent, rank=tuple(rank),
    )


def check_programs(plan: Plan, config: dict) -> None:
    """Fail before boot if a whole wave can land outside the list the
    configuration compiles (the key-table warm-up's g1dec apart)."""
    listed = set(config["programs"])
    wave_listed = {p for p in listed if not p.startswith("g1dec@")}
    shapes = plan.flush_shapes()
    if shapes != wave_listed:
        per_slot = sorted({plan.duties_in(p) for p in range(plan.slots_per_epoch)})
        raise TrafficError(
            f"the traffic's whole waves land on {sorted(shapes)} but the "
            f"configuration compiles {sorted(wave_listed)}: duties a slot "
            f"{per_slot}, {plan.senders()} sets a wave "
            f"(lanes {[d * plan.senders() for d in per_slot]})")
