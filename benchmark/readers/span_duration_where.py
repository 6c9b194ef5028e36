"""As span_duration, of the spans whose attribute `positive` is over 0
only (`cryptoplane.window` with `verify_jobs`: the windows that held a
verify wave, apart from the recombine windows): the median of their
durations. None where the node's ring cannot be read whole, or no span
called `span` in the window carries the attribute (a program from before
the attribute: the metric is left out of the line)."""

import statistics

from benchmark import nodespans


def read(run, span: str, positive: str):
    spans = nodespans.node_spans()
    if spans is None:
        return None
    values = [s.end - s.start for s in nodespans.window_spans(run, spans, span)
              if (s.attrs.get(positive) or 0) > 0]
    return statistics.median(values) if values else None
