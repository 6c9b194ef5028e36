#!/usr/bin/env python3
"""The cell `dv-3of4-1k-sync.attest-sync`'s control flow on the CPU:
rehearse_sync.py's two-kind run (host-only node, the tests' 3-of-4 cluster of
14 validators, a sync committee of 5, an attester wave and a sync-message
wave both due at 1/3 of every 3 s slot) THROUGH the crypto-plane service
path (planepatch: wave hints passed on; windows of 1.0 / 1.5 s, the longest a
kind waits for an awaited set: four nodes share this one interpreter, and a
wave's sets trail each other by up to half a second here),
`--trace 1` over the recorded trace, its cell reporting the per-layer
metrics the chip cell reports: `python benchmark/tests/rehearse_twokinds.py
[--forged] [run.py's own options]`. `--forged`: operator 4 flips a byte of
one partial of its SYNC set in the last slot (its attester set stays
honest). After the run's last line it prints ONE more stdout line, for the
tests: the node's own spans and every flush of the window with its duty
types, jobs, lanes, bucket, what closed its window, when its device stage
began and what it yielded the lane for."""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

CHIP_CELL = "dv-3of4-1k-sync.attest-sync"
FIELDS = ("duty_types", "jobs", "lanes", "verify_jobs", "recombine_jobs", "window_closed_by",
          "window_parts", "sets_expected", "sets_seen", "sets_awaited", "sets_invalid",
          "turn_yielded_s", "turn_yielded_to")


def make_root(tmp: Path) -> Path:
    """rehearse_sync's root, its two cells reporting what the chip cell
    reports (the manifest's lists that name it)."""
    from benchmark.tests import rehearse_sync

    root = rehearse_sync.make_root(tmp)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = [f"rehearsal-sync.{mix['name']}" for mix in (rehearse_sync.MIX, rehearse_sync.FORGED)]
    for m in manifest["per_layer"]:
        if CHIP_CELL in m["workloads"]:
            m["workloads"] = m["workloads"] + cells
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def main(argv) -> int:
    from benchmark import run, traffic
    from benchmark.tests import helpers, planepatch, rehearse_sync
    from charon_tpu.app import tracer
    from charon_tpu.core import cryptoplane

    argv, built = list(argv), {}
    mix = rehearse_sync.FORGED if "--forged" in argv else rehearse_sync.MIX
    if "--forged" in argv:
        argv.remove("--forged")

    def host_plane(server):
        planepatch.host_plane(server, handle=planepatch.Hinted, window=1.0, window_max=1.5)
        built["run"] = server.run
        # the chip node warms its key table at boot (the device's g1dec);
        # this host-only one has nobody to: decode the pubshares here, in
        # the set-up, or the first slot's sets trail each other by a second
        for shares in server.node.pubshares_by_idx.values():
            for pubshare in shares.values():
                cryptoplane._decode_pubkey(pubshare)

    helpers.fake_trace()
    with tempfile.TemporaryDirectory(prefix="bench_twokinds_") as tmp:
        args = ["--workload", f"rehearsal-sync.{mix['name']}", "--seed", "3800000011",
                "--seconds", "6", "--trace", "1", *argv]
        try:
            code = run.main(args, root=make_root(Path(tmp)), exit_fn=sys.exit,
                            rehearsal=run.Rehearsal(cpu=True, patch=host_plane))
        except SystemExit as e:  # the run's own exit, its last line printed
            code = e.code
    data = built["run"]
    flushes = []
    for ts, s in data.flushes:
        if data.in_window(ts):
            family = "verify_rlc_dec" if s.verify_jobs else "step_rlc_dec"
            flushes.append({
                **{f: getattr(s, f, None) for f in FIELDS},  # None: a program from before the field
                "slot": int((ts - data.window[0]) // data.slot_duration),
                "device_from_s": s.device_span and round(s.device_span[0] - data.window[0], 4),
                "window_s": [round(t - data.window[0], 4) for t in s.window_span],
                "program": f"{family}@{traffic.bucket_lanes(s.lanes)}"})
    print(json.dumps({
        "spans": [s for t in tracer.node_tracers().values() for s in t.dump()],
        "flushes": flushes,
    }), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
