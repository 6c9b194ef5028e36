"""The most duty types any flush of the window held
(`FlushStats.duty_types`, PR 39): 1.0 where every flush is one kind of
duty's, on that kind's own bucket; 2.0 if two kinds triggered at the same
instant ever merged into one program. None where no flush says its duty
types (a program from before the field: the metric is left out of the
line)."""

from benchmark import spans


def read(run):
    kinds = [len(s.duty_types) for _ts, s in spans.window_flushes(run)
             if getattr(s, "duty_types", None) is not None]
    return float(max(kinds)) if kinds else None
