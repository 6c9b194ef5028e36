"""Slots of the window whose FIRST verify dispatch was of another kind of
duty than the first slot's (`FlushStats.duty_types`, the flush's device
stage): 0.0 where the order in which two kinds due at the same instant take
the device is the node's rule — the same in every slot, whichever wave's
last set came first — and one a slot in which it was a race. None where no
verify flush says its duty types (a program from before the field: the
metric is left out of the line)."""

from benchmark import spans


def read(run):
    first = {}  # slot of the window -> (device stage's start, kinds)
    for _ts, s in spans.window_flushes(run):
        kinds = getattr(s, "duty_types", None)
        if kinds is None or not s.verify_jobs or not s.device_span:
            continue
        slot = int((s.device_span[0] - run.window[0]) // run.slot_duration)
        first[slot] = min(first.get(slot, (float("inf"), ())), (s.device_span[0], kinds))
    if not first:
        return None
    rule = first[min(first)][1]
    return float(sum(kinds != rule for _at, kinds in first.values()))
