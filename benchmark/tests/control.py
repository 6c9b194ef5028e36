#!/usr/bin/env python3
"""The control of `correct`, on the chip at a cell's own size:

    python3 benchmark/tests/control.py --workload <cell> --seed <n> --seconds <s> --trace 0

A run of benchmark/run.py in every respect but one: once the node is
built, helpers.unchecked_recombine stands in for its recombination. The
run has to end with `correct` false and `aggregates_differ` over its limit.
The benchmark's own runs never come here."""

from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

if __name__ == "__main__":
    from benchmark import run
    from benchmark.tests import helpers

    sys.exit(run.main(sys.argv[1:],
                      rehearsal=run.Rehearsal(patch=helpers.unchecked_recombine)))
