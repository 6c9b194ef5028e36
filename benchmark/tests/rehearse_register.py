#!/usr/bin/env python3
"""A duty that validator clients start, on the CPU: rehearse.py's run
(host-only node, the tests' 3-of-4 cluster, 14 validators, 2 slots of 3 s)
under a mix whose `duties` are `["attester", "registration"]` — the attester
wave every slot and, in the one slot of the window that is a multiple of
`registration_every_slots` = 2, a batch of `registrations_per_batch` = 6
builder registrations from every operator's VC in one request:

    python benchmark/tests/rehearse_register.py [--silent | --forged]
        [--unpatched | --without <patch>] [--plane] [--late <seconds>]
        [--requires <dotted.name>] [--patch <name>] [run.py's own options]

`--silent`: operator 2 sends nothing (every duty on bare quorum).
`--forged`: operator 4 flips a byte of ONE partial of its registration set
(its attester sets stay honest). Both at once the generator refuses on a
3-of-4 cluster: two honest speakers are fewer than t.

THE PATCHES (registerpatch.py, one a gap of the parent program, each named
for the change in `charon_tpu/` it stands for) are all on by default;
`--unpatched` runs the parent program as it is and `--without <patch>` all
but one, so that what each gap costs is on record. `--plane` patches the
crypto-plane service path in (planepatch: wave hints passed on, the cells'
windows of 0.3 / 0.6 s), where a set of one registration waits out a window
of its own; `--late <seconds>` opens the window no sooner than that after
genesis (a chip run's set-up is minutes: slot 0's deadline is 30 s).
`--seconds 18` (run.py's own) is a window of six slots, which holds an epoch's
start after a batch wherever it opens: the node's recaster re-sends that
batch there, and `duties/registration.submitted` has to tell it from a second
broadcast.

The configuration lists the programs of the two kinds' whole waves (the
attester's 3-4 duties x 4 senders: `verify_rlc_dec@16` / `step_rlc_dec@4`;
the batch's 6 x 4: `@32` / `@8`), which `traffic.check_programs` holds it to
before boot; the node is host-only and dispatches none of them.

After the run's last line, ONE more stdout line: when the VC's round and each
peer's set went out against the instant they were due, the spans the peers
appended, the flushes of the window. The configuration and the mixes exist in
the tests' own root alone: BENCHMARK.json has no such cell."""

from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

BATCH, EVERY = 6, 2
MIX = {
    "name": "attest-register",
    "description": "tests only: the attester wave at 1/3 of every slot and, at the start of "
                   "every second slot, a batch of builder registrations from every VC",
    "duties": ["attester", "registration"], "slots": "window", "send_jitter_ms": 30,
    "silent_operators": [], "fault": {"kind": "none"},
}
SILENT = dict(MIX, name="attest-register-silent", silent_operators=[2])
FORGED = dict(MIX, name="attest-register-forged", fault={
    "kind": "flip_byte", "operator": "last", "slots": "all", "partials": 1,
    "duties": ["registration"]})
REQUIRES = ["charon_tpu.core.validatorapi.ValidatorAPI.submit_registration"]


def make_root(tmp: Path, requires=()) -> Path:
    """helpers.make_root's tiny configuration with the registration kind's
    two sizes and the batch's own buckets beside the attester wave's, under
    the three mixes."""
    from benchmark.tests import helpers

    root = helpers.make_root(tmp, rehearsal=True)
    config = dict(helpers.REHEARSAL, name="rehearsal-reg",
                  registrations_per_batch=BATCH, registration_every_slots=EVERY,
                  requires=[*REQUIRES, *requires],
                  programs=["verify_rlc_dec@16", "step_rlc_dec@4", "verify_rlc_dec@32",
                            "step_rlc_dec@8", "g1dec@512"])
    (root / "benchmark" / "configs" / "rehearsal-reg.json").write_text(json.dumps(config))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "rehearsal-reg", "source": config["source"],
        "file": "benchmark/configs/rehearsal-reg.json", "reduced": [], "why": "tests"})
    for mix in (MIX, SILENT, FORGED):
        (root / "benchmark" / "mixes" / f"{mix['name']}.json").write_text(json.dumps(mix))
        manifest["workloads"].append({
            "name": f"rehearsal-reg.{mix['name']}", "config": "rehearsal-reg",
            "traffic": mix["name"], "chips": 1, "why": "tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def watch_sends(seen: dict) -> None:
    """When each peer's set of a VC-started kind went out, and each VC round;
    how many objects of each kind the node's beacon got, counted or not."""
    from benchmark import serve

    seen["sends"], seen["rounds"], seen["delivered"] = [], [], {}
    send, started = serve.HostPeer._send, serve.Server._vc_started
    stamped = serve.Server._stamped

    def counted(self, kind, inner):
        async def got(*args):
            seen["delivered"][kind.NAME] = seen["delivered"].get(kind.NAME, 0) + 1
            await inner(*args)

        return stamped(self, kind, got)

    async def noted_send(self, duty, unsigned_set, share_idx):
        called = time.time()
        await send(self, duty, unsigned_set, share_idx)
        seen["sends"].append((str(duty.type), duty.slot, share_idx, called, time.time()))

    async def noted_round(self, kind, slot):
        seen["rounds"].append((kind.NAME, slot, time.time()))
        await started(self, kind, slot)

    serve.HostPeer._send, serve.Server._vc_started = noted_send, noted_round
    serve.Server._stamped = counted


def main(argv) -> int:
    from benchmark import run
    from benchmark.tests import helpers, registerpatch

    argv, seen, built = list(argv), {}, {}

    def flag(name):
        if name in argv:
            argv.remove(name)
            return True
        return False

    def option(name):
        if name in argv:
            i = argv.index(name)
            value = argv[i + 1]
            del argv[i:i + 2]
            return value
        return None

    mix = SILENT if flag("--silent") else FORGED if flag("--forged") else MIX
    patches = dict(registerpatch.PATCHES)
    if flag("--unpatched"):
        patches = {}
    while (name := option("--without")) is not None:
        del patches[name]
    extra = helpers.PATCHES[name] if (name := option("--patch")) else None
    requires = [name] if (name := option("--requires")) else []
    plane = flag("--plane")
    late = float(option("--late") or 0.0)

    def patch(server):
        built["server"] = server
        for p in patches.values():
            p(server)
        if extra is not None:
            extra(server)
        if plane:
            from benchmark.tests import planepatch

            planepatch.host_plane(server, handle=planepatch.Hinted, window=0.3, window_max=0.6)
        if late:
            run.PHASE["align"] += late
            open_window = server.open_window
            server.open_window = lambda slots: open_window(
                slots, lead=max(0.75, server.genesis + late - time.time()))

    patch.__name__ = "+".join([*patches, *([extra.__name__] if extra else [])]) or "none"

    with tempfile.TemporaryDirectory(prefix="bench_reg_") as tmp:
        root = make_root(Path(tmp), requires)
        watch_sends(seen)
        args = ["--workload", f"rehearsal-reg.{mix['name']}", "--seed", "4300000013",
                "--seconds", "6", "--trace", "0", *argv]
        try:
            code = run.main(args, root=root, exit_fn=sys.exit,
                            rehearsal=run.Rehearsal(cpu=True, patch=patch))
        except SystemExit as e:  # the run's own exit, its last line printed
            code = e.code
    if "server" in built:
        server = built["server"]
        data, plan = server.run, server.plan
        dues = {(d.kind, d.slot): d.due for d in data.duties}

        def late(kind, slot, at):
            return round(at - dues[(kind, slot)], 3) if (kind, slot) in dues else None

        print(json.dumps({
            "window_slots": data.slots,
            "vc_rounds": [[k, s, late(k, s, at)] for k, s, at in seen["rounds"]],
            # [share index, called after due, broadcast done after due, the plan's jitter]
            "peer_sends": sorted([idx, late("registration", s, called),
                                  late("registration", s, done), round(plan.jitter(idx, s), 3)]
                                 for t, s, idx, called, done in seen["sends"]
                                 if t == "builder_registration"),
            "qbft_decided_spans": sum(1 for n, _a, _b in data.spans if n == "qbft_decided"),
            "peers": {p.index + 1: {"sent_sets": p.sent_sets, "forged_sets": p.forged_sets}
                      for p in server.peers},
            "flushes": [
                {"at_s": round(ts - data.window[0], 3), "duty_types": list(f.duty_types),
                 "verify_jobs": f.verify_jobs, "recombine_jobs": f.recombine_jobs,
                 "lanes": f.lanes, "window_s": round(f.window, 3),
                 "closed_by": f.window_closed_by}
                for ts, f in data.flushes if data.in_window(ts)],
            "vc_spans_s": {n: round(b - a, 3) for n, a, b in data.spans
                           if n in ("vc_registrations",) or (
                               n in ("vc_sign", "http_submit") and any(
                                   k == "registration" and abs(a - at) < 1.5
                                   for k, _s, at in seen["rounds"]))},
            # what the beacon got of each kind against the duties' records: the
            # recaster's epochly re-sends are deliveries and no broadcasts
            "delivered": seen["delivered"],
            "records": {k.NAME: sum(1 for d in data.duties if d.kind == k.NAME)
                        for k in plan.kinds},
            "patches": list(patches),
        }), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
