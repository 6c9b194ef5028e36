"""The one general traffic generator: a mix is a data file
(mixes/<name>.json), this turns it and --seed into a plan.

The plan fixes, before anything boots: the kinds of duty the mix names
(each a module, duties/<kind>.py, found by name), the validators' seeded
order (a permutation: a slot's share of it has floor(v/spe) or ceil(v/spe)
validators for every seed), from which every kind draws who holds its duty
in which slot, the per-peer send jitter, the fault (none / flip_byte /
wrong_key) and the silent operators — and from those every (family, bucket)
a whole wave's flushes can land on, which must be the configuration's
programs."""

from __future__ import annotations

import dataclasses
import hashlib
import random

from benchmark import manifest


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
    duties: tuple[str, ...] = ()  # the kinds whose sets are forged (none named: all)


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
    # the mix's duty modules, in its order, and the configuration file (a
    # kind may read sizes of its own there); both follow from the fields above
    kinds: tuple = dataclasses.field(default=(), compare=False, repr=False)
    sizes: dict = dataclasses.field(default_factory=dict, compare=False, repr=False)

    def members(self, slot: int) -> list[int]:
        """The validators of `slot`'s share of the seeded order, every
        validator in one slot of the epoch, in the order's order."""
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
        """A root of the seeded chain, the same on every operator's beacon."""
        return hashlib.sha256(
            ("att/%d/" % self.seed + "/".join(str(p) for p in parts)).encode()
        ).digest()

    def forged(self, slot: int, share_idx: int, last_slot: int, kind: str | None = None) -> bool:
        if self.fault.kind == "none" or share_idx != self.fault.operator:
            return False
        if self.fault.duties and kind not in self.fault.duties:
            return False
        return self.fault.slots == "all" or slot == last_slot

    def wave_shapes(self, duties: int) -> set[str]:
        """The `family@bucket` of a whole wave of `duties` duties, one
        partial each from every speaking operator: one verify flush of
        their sets, one recombine flush of the duties (decode on the
        device). A forged partial, flipped or well formed, is answered
        inside the verify program (per set since PR 36): no further shape."""
        if duties == 0:
            return set()
        return {f"verify_rlc_dec@{bucket_lanes(duties * self.senders())}",
                f"step_rlc_dec@{bucket_lanes(duties)}"}

    def flush_shapes(self) -> set[str]:
        """Every `family@bucket` a WHOLE wave lands on, of each of the
        mix's kinds alone. Two kinds whose flushes merge in one window
        land outside this set, and the run then fails fast."""
        return set().union(*(kind.shapes(self) for kind in self.kinds))


def make_plan(config: dict, traffic: dict, seed: int, bdir=None) -> Plan:
    """`bdir` is the benchmark's directory, where duties/<kind>.py are
    looked for (default: beside this file)."""
    duties = tuple(traffic.get("duties", ()))
    if not duties or len(set(duties)) != len(duties):
        raise TrafficError(f"traffic {traffic.get('name')!r}: duties {list(duties)}")
    try:
        kinds = tuple(manifest.load_duty(name, bdir) for name in duties)
    except manifest.ManifestError as e:
        raise TrafficError(f"traffic {traffic.get('name')!r}: {e}") from e
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
    if not set(f.get("duties", ())) <= set(duties):
        raise TrafficError(f"fault duties {f['duties']}: the mix's are {list(duties)}")
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
        fault=Fault(kind, operator, f.get("slots", "last"), int(f.get("partials", 1)),
                    tuple(f.get("duties", ()))),
        silent=silent, rank=tuple(rank), kinds=kinds, sizes=config,
    )


def check_programs(plan: Plan, config: dict) -> None:
    """Fail before boot if a whole wave can land outside the list the
    configuration compiles (the key-table warm-up's g1dec apart)."""
    listed = set(config["programs"])
    wave_listed = {p for p in listed if not p.startswith("g1dec@")}
    shapes = plan.flush_shapes()
    if shapes != wave_listed:
        per_slot = {kind.NAME: sorted({len(kind.members(plan, p))
                                       for p in range(plan.slots_per_epoch)})
                    for kind in plan.kinds}
        lanes = {name: [d * plan.senders() for d in sizes] for name, sizes in per_slot.items()}
        raise TrafficError(
            f"the traffic's whole waves land on {sorted(shapes)} but the "
            f"configuration compiles {sorted(wave_listed)}: duties a slot "
            f"{per_slot}, {plan.senders()} sets a wave (lanes {lanes})")
