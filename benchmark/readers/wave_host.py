"""Per wave: trigger due -> last broadcast, minus the union of the compiled
programs' spans inside it (host clock around dispatch + sync): what the
host workflow takes. The median over the window's complete waves."""

import statistics

from benchmark import spans
from benchmark.tracered import merge


def read(run):
    values = []
    programs = spans.program_intervals(run)
    for w in run.waves():
        if w["last_done"] is None:
            continue
        a, b = w["due"], w["last_done"]
        inside = merge([(max(a, s), min(b, e)) for _f, s, e in programs if e > a and s < b])
        values.append((b - a) - sum(e - s for s, e in inside))
    return statistics.median(values) if values else None
