"""The harness's own spans around the node, from outside: what the host
was doing between a wave's trigger and its last broadcast. Wall clock."""

from __future__ import annotations


def program_intervals(run, family_prefix: str | None = None):
    """(family, start, end) of every compiled-program dispatch the plane
    reported inside the window (host clock around dispatch + sync)."""
    return [
        (family, end - seconds, end)
        for family, seconds, _lanes, end in run.programs
        if run.in_window(end) and (family_prefix is None or family.startswith(family_prefix))
    ]


def window_flushes(run):
    return [(ts, s) for ts, s in run.flushes if run.in_window(ts)]


def wave_spans(run) -> list[tuple[str, float, float]]:
    """Named spans for the idle-gap labels: QBFT decision, the VC's HTTP
    round trips, window wait, pack, program, broadcast."""
    out = list(run.spans)
    decided = sorted(a for n, a, _b in run.spans if n == "qbft_decided")
    for w in run.waves():
        first = next((t for t in decided if t >= w["due"]), None)
        if first is not None:
            out.append(("qbft_decision", w["due"], first))
    for _ts, s in window_flushes(run):
        if s.pack_span:
            out.append(("window_wait", s.pack_span[0] - s.window, s.pack_span[0]))
            out.append(("pack", *s.pack_span))
        if s.device_span:
            out.append(("program", *s.device_span))
    for w in run.waves():
        ends = [b for _f, _a, b in program_intervals(run, "step")
                if w["due"] <= b <= w["due"] + run.slot_duration]
        if ends and w["last_done"]:
            out.append(("broadcast", max(ends), w["last_done"]))
    starts = sorted(w["due"] - run.slot_duration / 3 for w in run.waves())
    for s0 in starts:
        out.append(("slot_idle_before_trigger", s0, s0 + run.slot_duration / 3))
    return [(n, a, b) for n, a, b in out if b > a]
