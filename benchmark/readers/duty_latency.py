"""Seconds from the instant a duty's trigger was DUE on the slot clock to
the instant the node's beacon received its aggregate, over ALL duties of
the window; the q-th percentile by nearest rank. A duty never broadcast
counts with the time it was waited for."""

import math


def read(run, q: float):
    lat = sorted((d.done if d.done is not None else run.gave_up) - d.due
                 for d in run.duties)
    if not lat:
        return None
    return lat[max(0, math.ceil(q / 100.0 * len(lat)) - 1)]
