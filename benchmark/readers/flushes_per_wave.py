"""Flushes in the window over waves: 2 when no wave splits."""

from benchmark import spans


def read(run):
    waves, flushes = len(run.waves()), len(spans.window_flushes(run))
    return flushes / waves if waves and flushes else None
