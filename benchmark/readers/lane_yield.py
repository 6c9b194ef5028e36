"""Seconds the flushes of a slot yielded their device turn to a more urgent
kind of duty still collecting in the armed window
(`FlushStats.turn_yielded_s`: packed and ready, a flush lets the kind the
lane's order puts first go first): summed over the slot's flushes, the
median over the window's slots. 0.0 where nothing ever yielded (the more
urgent wave was whole first in most slots, or one kind of duty alone). What
the rule costs the device in idle time. None where no flush has the field
(a program from before it: the metric is left out of the line)."""

import statistics

from benchmark import spans


def read(run):
    per_slot = dict.fromkeys(range(len(run.slots)), 0.0)
    found = False
    for ts, s in spans.window_flushes(run):
        yielded = getattr(s, "turn_yielded_s", None)
        if yielded is None:
            continue
        found = True
        slot = int((ts - run.window[0]) // run.slot_duration)
        if slot in per_slot:
            per_slot[slot] += yielded
    return float(statistics.median(per_slot.values())) if found else None
