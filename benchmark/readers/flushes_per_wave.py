"""Flushes in the window over waves: 2 when no wave splits. A wave is one
kind of duty in one slot (a slot of k kinds holds k waves, a slot in which a
kind has no duty none of that kind)."""

from benchmark import spans


def read(run):
    waves = len({(d.slot, d.kind) for d in run.duties})
    flushes = len(spans.window_flushes(run))
    return flushes / waves if waves and flushes else None
