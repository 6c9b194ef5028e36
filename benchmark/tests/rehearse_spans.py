#!/usr/bin/env python3
"""rehearse.py with the crypto-plane service path patched in (planepatch), the
recorded trace standing in for the profiler and --trace 1: `python
benchmark/tests/rehearse_spans.py [run.py's own options]`. After the run's
last line it prints ONE more stdout line, for the tests: what the node's own
tracer holds once the node is torn down, beside what the process-global one
(the peers') holds, and what the node's span hook counted."""

from __future__ import annotations

import collections
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main(argv) -> int:
    from benchmark.tests import helpers, planepatch, rehearse
    from charon_tpu.app import tracer

    built = {}

    def host_plane(server):
        planepatch.host_plane(server)
        built["node"] = server.node

    helpers.PATCHES["host_plane"] = host_plane
    try:
        rehearse.main(["--patch", "host_plane", "--fake-trace", "--trace", "1", *argv])
    except SystemExit as e:  # the run's own exit, its last line printed
        code = e.code
    hooked = {
        sample.labels["step"]: int(sample.value)
        for family in built["node"].metrics.step_latency.collect()
        for sample in family.samples if sample.name.endswith("_count")
    }
    print(json.dumps({
        "nodes": {
            str(index): {"evicted": t.evicted, "capacity": t.spans.maxlen, "spans": t.dump()}
            for index, t in tracer.node_tracers().items()
        },
        "global": collections.Counter(s.name for s in tracer.global_tracer().spans),
        "hooked": hooked,
    }), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
