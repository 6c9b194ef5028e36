"""The node's own span `span` (app/tracer, wall clock at both ends), every
one that started in the window, each physical span once: the median of
their durations. None where the node's ring cannot be read whole."""

import statistics

from benchmark import nodespans


def read(run, span: str):
    spans = nodespans.node_spans()
    if spans is None:
        return None
    values = [s.end - s.start for s in nodespans.window_spans(run, spans, span)]
    return statistics.median(values) if values else None
