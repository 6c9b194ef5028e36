"""FlushStats of the window's flushes that held a verify wave whose jobs
all said how many sets the wave expects: sets expected minus sets seen (the
coalescer's own per-window ledger), the median over those flushes — one a
wave where no wave splits. 0 on a cluster whose every set comes; k with k
operators silent. None where no flush carries the two fields (a program
from before them: the metric is left out of the line)."""

import statistics

from benchmark import spans


def read(run):
    values = [float(s.sets_expected - s.sets_seen)
              for _ts, s in spans.window_flushes(run)
              if getattr(s, "sets_expected", None) is not None]
    return statistics.median(values) if values else None
