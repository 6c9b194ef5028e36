"""How many of the node's own spans called `span` whose attribute `attr`
is `value` started in each slot of the window (each physical span once):
the median over the window's slots. `vapi.submit` with `duty_type`
`sync_message`: the submissions the validator client's sync-committee
messages of a slot reach the node's plane as — 1.0 where a request is one
set (PR 39), the committee's size where each message is submitted alone.
None where the node's ring cannot be read whole or holds no such span."""

import statistics

from benchmark import nodespans


def read(run, span: str, attr: str, value: str):
    recorded = nodespans.node_spans()
    if recorded is None:
        return None
    mine = [s for s in nodespans.window_spans(run, recorded, span)
            if s.attrs.get(attr) == value]
    if not mine:
        return None
    start, per_slot = run.window[0], dict.fromkeys(range(len(run.slots)), 0)
    for s in mine:
        k = int((s.start - start) // run.slot_duration)
        if k in per_slot:
            per_slot[k] += 1
    return float(statistics.median(per_slot.values()))
