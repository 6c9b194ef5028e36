"""As program_seconds, of ONE kind of duty: the compiled programs of
`family` dispatched inside the device stage of the window's flushes whose
jobs were all of duty type `duty_type` (`FlushStats.duty_types`, PR 39: a
flush holds one kind), host clock around dispatch + sync, median per call.
None where no flush says its duty types (a program from before the field:
the metric is left out of the line) or none of that kind dispatched one."""

import statistics

from benchmark import spans

SLACK = 0.005  # the hook's end is stamped a beat after the program's


def read(run, family: str, duty_type: str):
    stages = [s.device_span for _ts, s in spans.window_flushes(run)
              if getattr(s, "duty_types", None) == (duty_type,) and s.device_span]
    values = [end - start for _f, start, end in spans.program_intervals(run, family)
              if any(a - SLACK <= start and end <= b + SLACK for a, b in stages)]
    return statistics.median(values) if values else None
