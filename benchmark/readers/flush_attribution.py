"""FlushStats of the window's verify flushes: the lanes the plane answered
False (`lanes_invalid`), summed over each wave's verify flushes (a wave is
the flushes that ended in one slot), the median over waves — 1.0 where one
forged partial a slot rides each wave's one verify flush, 0 on an honest
cluster. None where no flush carries the field (a program from before it:
the metric is left out of the line)."""

import statistics

from benchmark import spans


def read(run):
    start = run.window[0]
    waves: dict[int, float] = {}
    for ts, s in spans.window_flushes(run):
        if getattr(s, "lanes_invalid", None) is None or not getattr(s, "verify_jobs", 0):
            continue
        slot = int((ts - start) // run.slot_duration)
        waves[slot] = waves.get(slot, 0.0) + float(s.lanes_invalid)
    return statistics.median(waves.values()) if waves else None
