"""FlushStats of ONE kind of duty's VERIFY flushes: over the window's
flushes whose jobs were all of duty type `duty_type`
(`FlushStats.duty_types`, PR 39: a flush holds one kind) and that held a
verify job, median per flush.
field = hashed: the signing roots the flush's decode chunks hashed to G2
(`sum(FlushStats.decode_hashed)`: every lane's own root in a wave of builder
registrations, where an attester wave hashes one a duty and a sync wave one);
field = pack: what `flush_stat` reads for `pack`, the decode chunks' spans
plus the pack span — the host seconds that hashing costs the flush.
None where no flush says its duty types (a program from before the field),
none of that kind verified anything, or the field was not recorded: the
metric is left out of the line."""

import statistics

from benchmark import spans


def read(run, field: str, duty_type: str):
    if field not in ("hashed", "pack"):
        raise ValueError(f"flush_stat_of_kind: no field {field!r}")
    values = []
    for _ts, s in spans.window_flushes(run):
        if getattr(s, "duty_types", None) != (duty_type,) or not getattr(s, "verify_jobs", 0):
            continue
        if field == "hashed":
            values.append(float(sum(s.decode_hashed)))
        elif s.pack_span is not None:
            values.append((s.pack_span[1] - s.pack_span[0])
                          + sum(b - a for a, b in s.decode_spans))
    return statistics.median(values) if values else None
