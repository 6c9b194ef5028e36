"""From a profiler trace (.xplane.pb) to numbers: device busy seconds, the
device-side duration of each XLA module, the operations that took most
time, the longest idle gaps. Read with nothing but jax's ProfileData.

Event times in the file are nanoseconds from the start of the trace; the
harness notes the wall clock at start_trace, so its own spans (wall clock)
and the device's events share one axis."""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import time
from pathlib import Path

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def start(jax, root: Path) -> dict:
    """Begin tracing into a directory of the checkout's cache (removed
    again by stop); python and host tracing off: they slow the host that is
    timed and lengthen the end of the trace."""
    out = root / "benchmark" / ".cache" / "trace"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0  # device planes are all the reducer reads
    wall = time.time()
    jax.profiler.start_trace(str(out), profiler_options=opts)
    return {"dir": out, "wall": wall}


def stop(jax, handle: dict, note=lambda text: None) -> "TraceSummary":
    stopped = time.time()
    jax.profiler.stop_trace()
    note(f"trace: stop_trace took {time.time() - stopped:.1f} s")
    files = glob.glob(str(handle["dir"] / "plugins" / "profile" / "*" / "*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler left no .xplane.pb under {handle['dir']}")
    t0 = time.time()
    summary = reduce_file(files[0], handle["wall"], stopped - handle["wall"])
    note(f"trace: {os.path.getsize(files[0])} bytes, {summary.events} device events, "
         f"reduced in {time.time() - t0:.1f} s")
    shutil.rmtree(handle["dir"], ignore_errors=True)
    return summary


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class TraceSummary:
    wall_start: float  # wall clock at start_trace
    window_s: float  # length of the traced window
    devices: int  # device planes with at least one operation
    busy_s: float  # union of device-op intervals, averaged over devices
    busy: list  # device 0: merged (start, end), seconds from trace start
    modules: list  # device 0: (name, start, seconds), in time order
    op_seconds: list  # device 0: (name, seconds, calls), most time first
    events: int

    def idle_gaps(self) -> list[tuple[float, float]]:
        """Device 0's idle intervals inside the window, longest first."""
        gaps, at = [], 0.0
        for a, b in self.busy:
            if a > at:
                gaps.append((at, a))
            at = max(at, b)
        if self.window_s > at:
            gaps.append((at, self.window_s))
        return sorted(gaps, key=lambda g: g[0] - g[1])

    def breakdown(self, run=None) -> dict:
        """The contract's `breakdown`: top device operations and the
        longest idle gaps, each gap named by the harness span that
        covers most of it."""
        spans = []
        if run is not None:
            from benchmark import spans as spanlib

            spans = [(n, a - self.wall_start, b - self.wall_start)
                     for n, a, b in spanlib.wave_spans(run)]
        gaps = []
        for a, b in self.idle_gaps()[:10]:
            best, cover = "unattributed", 0.0
            for name, sa, sb in spans:
                c = min(b, sb) - max(a, sa)
                if c > cover:
                    best, cover = name, c
            gaps.append([best, b - a])
        return {
            "device_ops": [[short_name(n), s] for n, s, _c in self.op_seconds[:10]],
            "idle_gaps": gaps,
        }


def short_name(hlo: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` -> `%fusion.3`."""
    return hlo.split(" = ", 1)[0][:80]


def reduce_file(path: str, wall_start: float, window_s: float) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    per_device = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        ops_line = lines.get(OPS_LINE) or lines.get(MODULE_LINE)
        if ops_line is None:
            continue
        ops = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in ops_line.events]
        if not ops:
            continue
        mods = []
        if MODULE_LINE in lines:
            mods = sorted(
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for e in lines[MODULE_LINE].events
            )
            mods.sort(key=lambda m: m[1])
        per_device.append((plane.name, ops, mods))
    if not per_device:
        raise RuntimeError("the trace holds no device operation")
    per_device.sort(key=lambda d: d[0])
    busies = [merge([(s, s + d) for _n, s, d in ops]) for _p, ops, _m in per_device]
    busy_s = sum(sum(b - a for a, b in busy) for busy in busies) / len(busies)
    _name, ops0, mods0 = per_device[0]
    totals: dict[str, list] = {}
    for name, _s, d in ops0:
        t = totals.setdefault(name, [0.0, 0])
        t[0] += d
        t[1] += 1
    op_seconds = sorted(((n, t[0], t[1]) for n, t in totals.items()),
                        key=lambda x: -x[1])
    return TraceSummary(
        wall_start=wall_start, window_s=window_s, devices=len(per_device),
        busy_s=busy_s, busy=busies[0], modules=mods0, op_seconds=op_seconds,
        events=sum(len(ops) for _p, ops, _m in per_device),
    )
