#!/usr/bin/env python3
"""The served path's control flow on the CPU at a tiny size, host-only
(no plane): `python benchmark/tests/rehearse.py [--patch <name>]
[--fake-trace] [run.py's own options]`. `--patch` names one of
helpers.PATCHES (the control, or a break of the timed path underneath);
`--fake-trace` stands the recorded trace in for the profiler, which has
no device plane on the CPU."""

from __future__ import annotations

import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main(argv) -> int:
    from benchmark import run
    from benchmark.tests import helpers

    argv, patch = list(argv), None
    if "--patch" in argv:
        i = argv.index("--patch")
        patch = helpers.PATCHES[argv[i + 1]]
        del argv[i:i + 2]
    if "--fake-trace" in argv:
        argv.remove("--fake-trace")
        helpers.fake_trace()
    with tempfile.TemporaryDirectory(prefix="bench_rehearse_") as tmp:
        root = helpers.make_root(Path(tmp), rehearsal=True)
        args = ["--workload", "rehearsal.attest-slot", "--seed", "3000000007",
                "--seconds", "6", "--trace", "0"] + argv
        return run.main(args, root=root, exit_fn=sys.exit,
                        rehearsal=run.Rehearsal(cpu=True, patch=patch))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
