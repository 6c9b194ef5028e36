"""Seconds of device 0's idle intervals in the traced window that the
node's own spans give to `cause` (benchmark/nodespans.py): at each idle
instant the innermost open span of ANY of the window's duties, of whatever
kind the mix names (`run.duty_types`), nearest the device first. Each idle
instant has exactly one cause, so the causes — these metrics plus
`pre_trigger` and `other`, which are noted on stderr and are no metric — sum
to `device.window_s - device.busy_s`."""

from benchmark import nodespans


def read(run, cause: str):
    total = nodespans.idle_seconds(run)
    return None if total is None else total[cause]
