#!/usr/bin/env python3
"""The verdicts of a flush that holds a forged partial against the plain
reference, set for set, on the chip at a cell's own size:

    python3 benchmark/tests/attribution.py --workload <cell> --seed <n> --seconds <s> --trace 0

A run of benchmark/run.py in every respect but one: once the node is built,
the handle through which its submitters (ValidatorAPI, the ParSigEx verifier,
SigAgg) reach the crypto plane remembers every verify job beside the answers
it was given, and every recombine row. After the run's last line — outside
the timed window, the node torn down — every lane of the window's FIRST wave
(the forged lane, the rest of the forger's set, the other sets) is verified
by benchmark/reference_verify.py in plain Python. A SET's verdict is the AND
over its lanes, on both sides (tests/test_set_verdicts.py): since PR 36 the
RLC program refuses a set whole, and the honest lanes of a refused set come
back `None` — not judged apart — where the per-lane tier said True. So every
set is compared, and beside it every lane that WAS judged apart (True or
False); then ONE more stdout line says how many sets and lanes were compared,
which differ, how many lanes were refused with their set, how long the
reference took, and the window's dispatch record: per wave the programs in
order, each flush's verify-tier fields, and the share indices of its
recombine rows. Exit code 0 where the run reached its end and every set and
every judged lane agrees. The benchmark's own runs never come here."""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


class Recorded:
    """The plane as the submitters hold it (a tenant's handle, or the
    coalescer itself), remembering what passes through it: every verify job
    — its lanes as (pubkey, root, signature) bytes, the answers it got, its
    sender where the wave hint names one — and every recombine row beside
    its aggregate."""

    def __init__(self, plane):
        self._plane, self.sets, self.rows = plane, [], []

    def __getattr__(self, name):  # t, wave_hints, tenant_id, ...
        return getattr(self._plane, name)

    async def verify(self, items, **kw):
        lanes = [tuple(bytes(x) for x in lane) for lane in items]
        answers = await self._plane.verify(lanes, **kw)
        hint = (kw.get("wave") or ((None, None),))[0][1]
        self.sets.append({"at": time.time(), "sender": getattr(hint, "sender", None),
                          "lanes": lanes, "answers": list(answers)})
        return answers

    async def recombine(self, pubshares, roots, partials, group_pks, indices, **kw):
        sigs, ok = await self._plane.recombine(
            pubshares, roots, partials, group_pks, indices, **kw)
        now = time.time()
        self.rows += [
            {"at": now, "group_pk": bytes(g), "root": bytes(r), "indices": list(i),
             "partials": [bytes(p) for p in row], "aggregate": bytes(s)}
            for g, r, i, row, s in zip(group_pks, roots, indices, partials, sigs)]
        return sigs, ok


def main(argv, root=None, cpu=False, before=None) -> int:
    """`root`, `cpu` and `before` (a patch that runs before the recorder's)
    are the tests': the script's own control flow on the CPU."""
    from benchmark import reference_verify, run, spans
    from charon_tpu.p2p.adapters import PARSIGEX_PROTOCOL

    built = {}

    def record_verdicts(server):
        if before is not None:
            before(server)
        node = server.node
        handle = Recorded(node.sigagg.plane)
        parsigex = node.p2p._handlers[PARSIGEX_PROTOCOL].__self__.local
        node.vapi.plane = parsigex.verifier.plane = node.sigagg.plane = handle
        built.update(server=server, handle=handle)

    def leave(code):
        raise SystemExit(code)

    try:
        code = run.main(argv, exit_fn=leave, **({} if root is None else {"root": root}),
                        rehearsal=run.Rehearsal(cpu=cpu, patch=record_verdicts))
    except SystemExit as e:  # the run's own exit, its last line printed
        code = e.code
    if "server" not in built:
        return code or 3
    server, handle = built["server"], built["handle"]
    data = server.run
    bucket = getattr(getattr(server.coalescer, "plane", None), "bucket_lanes", int)

    def wave_of(ts):
        return int((ts - data.window[0]) // data.slot_duration)

    first = [s for s in handle.sets if wave_of(s["at"]) == 0]
    t0, compared, differ, sets_differ, unjudged = time.monotonic(), 0, [], [], 0
    for s in first:
        sound = [reference_verify.verify(*lane) for lane in s["lanes"]]
        if all(sound) != all(s["answers"]):
            sets_differ.append({"sender": s["sender"], "served": all(s["answers"])})
        for pos, (want, answer) in enumerate(zip(sound, s["answers"])):
            if answer is None:  # refused with its set, not judged apart
                unjudged += 1
                continue
            compared += 1
            if want != answer:
                differ.append({"sender": s["sender"], "lane": pos, "served": answer})
    waves = []
    programs = sorted(data.programs, key=lambda p: p[3])
    for k, slot in enumerate(data.slots):
        inside = lambda ts, k=k: wave_of(ts) == k  # noqa: E731
        waves.append({
            "slot": slot,
            "programs": [f"{f}@{bucket(n)}" for f, _s, n, end in programs if inside(end)],
            "program_s": [round(s, 4) for _f, s, _n, end in programs if inside(end)],
            "flushes": [{f: getattr(st, f, None) for f in (
                "verify_jobs", "recombine_jobs", "lanes", "sets_expected", "sets_seen",
                "sets_awaited", "window_closed_by", "attributed", "lanes_invalid",
                "sets_invalid", "attribute_lanes")} for ts, st in spans.window_flushes(data)
                if inside(ts)],
            "sets": [{"sender": s["sender"], "lanes": len(s["lanes"]),
                      "invalid": s["answers"].count(False), "accepted": all(s["answers"])}
                     for s in handle.sets if inside(s["at"])],
            "row_indices": sorted({tuple(r["indices"]) for r in handle.rows if inside(r["at"])}),
            "rows": sum(1 for r in handle.rows if inside(r["at"])),
        })
    coalescer = server.coalescer
    print(json.dumps({"attribution": {
        "wave_slot": data.slots[0], "sets_of_the_wave": len(first),
        "sets_served_invalid": sum(1 for s in first if not all(s["answers"])),
        "sets_that_differ": sets_differ, "lanes_compared": compared,
        "lanes_refused_with_their_set": unjudged,
        "lanes_served_invalid": sum(s["answers"].count(False) for s in first),
        "lanes_that_differ": differ, "reference_seconds": round(time.monotonic() - t0, 2),
        "flushes_attributed": getattr(coalescer, "flushes_attributed", None),
        "flushes_set_resolved": getattr(coalescer, "flushes_set_resolved", None),
        "lanes_invalid": getattr(coalescer, "lanes_invalid", None), "waves": waves}}),
        flush=True)
    return 0 if code == 0 and first and not sets_differ and not differ else 1


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)  # threads of the node may not keep the process alive
