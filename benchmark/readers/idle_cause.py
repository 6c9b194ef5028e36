"""Seconds of device 0's idle intervals in the traced window that the
node's own spans of `duty` duties give to `cause` (benchmark/nodespans.py):
each idle instant has exactly one cause, so the causes — these metrics plus
`pre_trigger` and `other`, which are noted on stderr and are no metric —
sum to `device.window_s - device.busy_s`."""

from benchmark import nodespans


def read(run, cause: str, duty: str):
    total = nodespans.idle_seconds(run, duty)
    return None if total is None else total[cause]
