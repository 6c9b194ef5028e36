"""SlotCryptoPlane.on_program: host clock around dispatch + sync of each
compiled program of one family, median per call. Not device busy time."""

import statistics

from benchmark import spans


def read(run, family: str):
    values = [e - s for _f, s, e in spans.program_intervals(run, family)]
    return statistics.median(values) if values else None
