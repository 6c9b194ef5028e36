"""FlushStats of every flush in the window, median per flush.
field = window: the coalescing window in force when the flush armed;
field = pack: the decode chunks' spans plus the pack span."""

import statistics

from benchmark import spans


def read(run, field: str):
    values = []
    for _ts, s in spans.window_flushes(run):
        if field == "window":
            values.append(float(s.window))
        elif field == "pack":
            if s.pack_span is None:
                continue
            values.append((s.pack_span[1] - s.pack_span[0])
                          + sum(b - a for a, b in s.decode_spans))
        else:
            raise ValueError(f"flush_stat: no field {field!r}")
    return statistics.median(values) if values else None
