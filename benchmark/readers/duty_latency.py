"""Seconds from the instant a duty's trigger was DUE on the slot clock to
the instant the node's beacon received its aggregate, over ALL duties of
the window (with `kind`: over the duties of that kind alone); the q-th
percentile by nearest rank. A duty never broadcast counts with the time it
was waited for."""

import math


def read(run, q: float, kind: str | None = None):
    lat = sorted((d.done if d.done is not None else run.gave_up) - d.due
                 for d in run.duties if kind is None or d.kind == kind)
    if not lat:
        return None
    return lat[max(0, math.ceil(q / 100.0 * len(lat)) - 1)]
