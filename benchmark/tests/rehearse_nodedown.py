#!/usr/bin/env python3
"""The `node-down` mix's control flow on the CPU: rehearse_spans.py's run
(host-only node, the crypto-plane service path patched in over a plane that
runs no program, the recorded trace standing in for the profiler, --trace 1)
on a cluster AT BARE QUORUM — the tests' 3-of-4 configuration with one
operator silent — and with the wave hints passed through to the coalescer,
as a real plane gets them: `python benchmark/tests/rehearse_nodedown.py
[run.py's own options]`. Every verify window then holds 3 of the 4 sets its
submitters expect and falls to its timer; every recombine window holds its
one job and closes `complete`; every duty is made from the only t partials
there are. After the run's last line it prints ONE more stdout line, for the
tests: the node's own spans and every flush's FlushStats fields."""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

CELL = "rehearsal.one-down"
MIX = {
    "name": "one-down",
    "description": "tests only: mixes/node-down.json on four operators, one of them silent",
    "duties": ["attester"], "slots": "window", "send_jitter_ms": 30,
    "fault": {"kind": "none"}, "silent_operators": [3],
}


def make_root(tmp: Path) -> Path:
    """helpers.make_root's tiny 3-of-4 configuration, and beside its cell
    one more: the same configuration under MIX, reporting every per-layer
    metric the manifest has."""
    from benchmark.tests import helpers

    root = helpers.make_root(tmp, rehearsal=True)
    (root / "benchmark" / "mixes" / "one-down.json").write_text(json.dumps(MIX))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["workloads"].append({
        "name": CELL, "config": "rehearsal", "traffic": "one-down", "chips": 1,
        "why": "tests"})
    for m in manifest["per_layer"]:
        m["workloads"] = m["workloads"] + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def main(argv) -> int:
    from benchmark import run
    from benchmark.tests import helpers, planepatch
    from charon_tpu.app import tracer

    built = {}

    def host_plane(server):
        planepatch.host_plane(server, handle=planepatch.Hinted)
        built["run"] = server.run

    helpers.fake_trace()
    with tempfile.TemporaryDirectory(prefix="bench_nodedown_") as tmp:
        args = ["--workload", CELL, "--seed", "3000000007", "--seconds", "6",
                "--trace", "1", *argv]
        try:
            code = run.main(args, root=make_root(Path(tmp)), exit_fn=sys.exit,
                            rehearsal=run.Rehearsal(cpu=True, patch=host_plane))
        except SystemExit as e:  # the run's own exit, its last line printed
            code = e.code
    fields = ("jobs", "verify_jobs", "recombine_jobs", "sets_expected", "sets_seen",
              "window_closed_by", "window")
    print(json.dumps({
        "spans": [s for t in tracer.node_tracers().values() for s in t.dump()],
        "flushes": [{f: getattr(s, f) for f in fields}
                    for ts, s in built["run"].flushes if built["run"].in_window(ts)],
    }), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
