"""Self time of the node's own spans called one of `spans`, in the traces
of `duty` duties: per wave, the
measure of the instants at which one of them is open with no child span
open (the union over the wave's duty traces: parallel submissions and a
stage shared by several count once); the median over the window's complete
waves. None where the node's ring cannot be read whole."""

import statistics

from benchmark import nodespans


def read(run, spans: list, duty: str):
    recorded = nodespans.node_spans()
    if recorded is None:
        return None
    values = nodespans.wave_self_seconds(
        run, nodespans.duty_spans(run, recorded, [duty]), set(spans))
    return statistics.median(values) if values else None
