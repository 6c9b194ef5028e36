"""Profiler trace of the traced slot: the device-side duration of the XLA
modules that ran inside each dispatch of one program family, per call
(median). The plane's programs all print as jit_local(<id>) in the trace,
so a module is given to the family whose dispatch span covers it."""

import statistics

from benchmark import spans

SLACK = 0.05  # device and host clocks differ by about a millisecond


def read(run, family: str):
    trace = run.trace
    if trace is None:
        return None
    values = []
    for _f, s, e in spans.program_intervals(run, family):
        a, b = s - trace.wall_start - SLACK, e - trace.wall_start + SLACK
        inside = [d for _n, t, d in trace.modules if a <= t and t + d <= b]
        if inside:
            values.append(sum(inside))
    return statistics.median(values) if values else None
