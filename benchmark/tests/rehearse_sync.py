#!/usr/bin/env python3
"""Two kinds of duty in one mix, on the CPU: rehearse.py's run (host-only
node, tiny) under a mix whose `duties` are `["attester", "sync_message"]` —
the tests' 3-of-4 configuration with a sync committee of 5 of its 14
validators, every slot, beside the 3-4 attesters: `python
benchmark/tests/rehearse_sync.py [--forged] [--plane] [--patch <name>]
[run.py's own options]`. `--forged` takes the mix in which operator 4 flips a
byte of ONE partial of its sync-message set in the last slot (its attester
set stays honest); `--patch` names one of helpers.PATCHES; `--plane` patches
the crypto-plane service path in (planepatch: wave hints passed on, the
cells' windows of 0.3 / 0.6 s) and prints, after the run's last line, ONE
more stdout line: every flush of the window with its jobs, lanes and what
closed its window. The configuration and the two mixes
exist in the tests' own root alone: BENCHMARK.json has no such cell."""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

MIX = {
    "name": "attest-sync",
    "description": "tests only: an attester wave and a sync-committee message wave, both "
                   "triggered at 1/3 of every slot",
    "duties": ["attester", "sync_message"], "slots": "window", "send_jitter_ms": 30,
    "silent_operators": [], "fault": {"kind": "none"},
}
FORGED = dict(MIX, name="attest-sync-forged", fault={
    "kind": "flip_byte", "operator": "last", "slots": "last", "partials": 1,
    "duties": ["sync_message"]})


def make_root(tmp: Path) -> Path:
    """helpers.make_root's tiny configuration with a sync committee of 5 and
    the programs a whole wave of either kind lands on, under the two mixes."""
    from benchmark.tests import helpers

    root = helpers.make_root(tmp, rehearsal=True)
    config = dict(helpers.REHEARSAL, name="rehearsal-sync", sync_committee_members=5,
                  programs=["verify_rlc_dec@16", "step_rlc_dec@4", "verify_rlc_dec@32",
                            "step_rlc_dec@8", "g1dec@512"])
    (root / "benchmark" / "configs" / "rehearsal-sync.json").write_text(json.dumps(config))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "rehearsal-sync", "source": config["source"],
        "file": "benchmark/configs/rehearsal-sync.json", "reduced": [], "why": "tests"})
    for mix in (MIX, FORGED):
        (root / "benchmark" / "mixes" / f"{mix['name']}.json").write_text(json.dumps(mix))
        manifest["workloads"].append({
            "name": f"rehearsal-sync.{mix['name']}", "config": "rehearsal-sync",
            "traffic": mix["name"], "chips": 1, "why": "tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def main(argv) -> int:
    from benchmark import run
    from benchmark.tests import helpers

    argv, patches, built = list(argv), [], {}
    mix = FORGED if "--forged" in argv else MIX
    if "--forged" in argv:
        argv.remove("--forged")
    if "--plane" in argv:
        from benchmark.tests import planepatch

        argv.remove("--plane")
        patches.append(lambda server: planepatch.host_plane(
            server, handle=planepatch.Hinted, window=0.3, window_max=0.6))
    if "--patch" in argv:
        i = argv.index("--patch")
        patches.append(helpers.PATCHES[argv[i + 1]])
        del argv[i:i + 2]

    def patch(server):
        built["run"] = server.run
        for p in patches:
            p(server)

    patch.__name__ = "+".join(getattr(p, "__name__", "plane") for p in patches) or "none"

    with tempfile.TemporaryDirectory(prefix="bench_sync_") as tmp:
        args = ["--workload", f"rehearsal-sync.{mix['name']}", "--seed", "3700000011",
                "--seconds", "6", "--trace", "0", *argv]
        try:
            code = run.main(args, root=make_root(Path(tmp)), exit_fn=sys.exit,
                            rehearsal=run.Rehearsal(cpu=True, patch=patch))
        except SystemExit as e:  # the run's own exit, its last line printed
            code = e.code
    if "run" in built and built["run"].flushes:
        data = built["run"]
        print(json.dumps({"flushes": [
            {"at_s": round(ts - data.window[0], 3), "jobs": s.jobs, "lanes": s.lanes,
             "verify_jobs": s.verify_jobs, "recombine_jobs": s.recombine_jobs,
             "window_s": round(s.window, 3), "closed_by": s.window_closed_by,
             "sets_expected": s.sets_expected, "sets_seen": s.sets_seen}
            for ts, s in data.flushes if data.in_window(ts)]}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
