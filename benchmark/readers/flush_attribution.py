"""FlushStats of the window's verify flushes: what the plane refused, summed
over each wave's verify flushes (a wave is the flushes that ended in one
slot), the median over waves. `field` names the count: `sets_invalid`, the
partial-signature sets refused (1.0 where one forging operator's set rides
each wave's one verify flush, 0 on an honest cluster), or `lanes_invalid`,
the lanes answered False or refused with their set (the refused set's size
since PR 36). None where no flush carries the field (a program from before
it: the metric is left out of the line)."""

import statistics

from benchmark import spans


def read(run, field: str = "lanes_invalid"):
    start = run.window[0]
    waves: dict[int, float] = {}
    for ts, s in spans.window_flushes(run):
        if getattr(s, field, None) is None or not getattr(s, "verify_jobs", 0):
            continue
        slot = int((ts - start) // run.slot_duration)
        waves[slot] = waves.get(slot, 0.0) + float(getattr(s, field))
    return statistics.median(waves.values()) if waves else None
