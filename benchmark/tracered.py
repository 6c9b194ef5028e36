"""From a profiler trace to numbers: device busy seconds, the device-side
duration of each XLA module, the operations that took most time, the
longest idle gaps. Read with nothing but jax's ProfileData.

Event times in the trace are nanoseconds from the start of the trace; the
harness notes the wall clock when the session starts, so its own spans (wall
clock) and the device's events share one axis.

Ending a trace is the dearest thing a traced run does, and what
`jax.profiler.stop_trace` adds to it nothing here reads (my chip runs,
PR 34; README.md "--trace 1"): a `.xplane.pb` on disk and every event once
more as `<host>.trace.json.gz`. So the session is the profiler's own
(`jax._src.lib._profiler.ProfilerSession`, what `start_trace` wraps),
stopped into bytes and read from memory: no file is written."""

from __future__ import annotations

import dataclasses
import time

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def options(jax):
    """The one builder of the profiler's options (tests/record_trace.py
    records under them too): python and host tracing off — they slow the
    host that is timed, and the reducer reads device planes alone; no HLO
    proto — it rides along for every module compiled in the process, which
    in a cell's compiling run is the pairing programs."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def start(jax) -> dict:
    from jax._src.lib import _profiler

    jax.devices()  # the backend is up before the session looks for its tracers
    wall = time.time()
    return {"session": _profiler.ProfilerSession(options(jax)), "wall": wall}


def stop_bytes(handle: dict) -> bytes:
    """End the session: the serialised XSpace, an `.xplane.pb`'s bytes."""
    return handle.pop("session").stop()


def stop(handle: dict, note=lambda text: None) -> "TraceSummary":
    stopped = time.time()
    blob = stop_bytes(handle)
    ended = time.time()
    summary = reduce_bytes(blob, handle["wall"], stopped - handle["wall"])
    summary.stop_s, summary.bytes = ended - stopped, len(blob)
    note(f"trace: ended in {summary.stop_s:.1f} s, {len(blob)} bytes, {summary.events} "
         f"device events, reduced in {time.time() - ended:.1f} s")
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
    planes: dict  # {plane: {line: events}} of every line that holds any
    stop_s: float | None = None  # what ending the session took; None: read from a file
    bytes: int | None = None  # the serialised trace

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

    return reduce_data(ProfileData.from_file(path), wall_start, window_s)


def reduce_bytes(blob: bytes, wall_start: float, window_s: float) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_data(ProfileData.from_serialized_xspace(blob), wall_start, window_s)


def reduce_data(data, wall_start: float, window_s: float) -> TraceSummary:
    per_device, planes = [], {}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        ops_line = None
        if plane.name.startswith("/device:"):
            ops_line = lines.get(OPS_LINE) or lines.get(MODULE_LINE)
        ops = [] if ops_line is None else [
            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in ops_line.events]
        held = {name: len(ops) if line is ops_line else sum(1 for _ in line.events)
                for name, line in lines.items()}
        if any(held.values()):
            planes[plane.name] = {name: n for name, n in held.items() if n}
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
        events=sum(len(ops) for _p, ops, _m in per_device), planes=planes,
    )
