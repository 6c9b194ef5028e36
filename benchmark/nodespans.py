"""The node's own spans (app/tracer), read after the run: which layer of the
node the host was in, from inside, on the wall clock the device trace is
anchored to (`TraceSummary.wall_start`).

A span has `name`, `start`, `end` (wall clock), `attrs`, `span_id`,
`parent_id`, `trace_id`. A span's SELF time is its interval minus what its
children (by `parent_id`) cover; at any instant the spans that are open and
have no open child are the INNERMOST ones. One duty's VC submission and its
peers' sets run side by side in one trace, so there may be several.

Where the program has no per-node tracer (the parent of the PR that added
these readers), or the ring is not one whole node's, `node_spans` returns
None and every metric read from it is left out of the line."""

from __future__ import annotations

import sys

from benchmark.tracered import merge

# idle causes, nearest the device first; a span not named here is "other"
CAUSES = {
    "pack": ("cryptoplane.decode", "cryptoplane.pack"),
    "window": ("cryptosvc.queue", "cryptoplane.window"),
    "entry": ("vapi.submit", "parsigex.receive", "parsigex.verify",
              "parsigdb.store_internal", "parsigdb.store_external"),
    "consensus": ("qbft.instance", "qbft.deliver", "consensus.propose"),
}
ORDER = ("pack", "window", "entry", "consensus", "other")
NO_SPAN = ("pre_trigger", "awaiting_input")
_CAUSE_OF = {name: cause for cause, names in CAUSES.items() for name in names}


def node_spans():
    """The finished spans of the process's ONE node, oldest first — or
    None: no node registered its tracer, two did, or the ring wrapped."""
    try:
        from charon_tpu.app import tracer
    except ImportError:
        return None
    registry = getattr(tracer, "node_tracers", None)
    if registry is None:
        return None
    tracers = list(registry().values())
    if len(tracers) != 1 or tracers[0].evicted:
        return None
    return list(tracers[0].spans)


def duty_spans(run, spans, kinds):
    """The spans of the traces of the window's duties of the `kinds` named
    (["attester"]: duty types as a span's `duty` attribute spells them; a
    mix's kinds): a trace belongs where one of its spans names such a duty
    of one of the window's slots. The node runs other duties too, and
    their spans say nothing of a wave though they may be open all through
    it: an aggregator duty's fetch waits a slot and more for selection
    proofs no VC sends, and a duty from before the window that never
    reached a decision holds its consensus spans open until its deadline
    cancels them, slots later."""
    mine = {f"{slot}/{kind}" for slot in run.slots for kind in kinds}
    traces = {s.trace_id for s in spans if s.attrs.get("duty") in mine}
    return [s for s in spans if s.trace_id in traces]


def window_spans(run, spans, name: str):
    """Spans called `name` that started in the run's window, each physical
    span once: the bridge's copies (`shared`) and the zero-length marks of
    shed submissions are skipped."""
    return [s for s in spans
            if s.name == name and run.in_window(s.start)
            and not s.attrs.get("shared") and not s.attrs.get("shed")]


def by_parent(spans) -> dict:
    children: dict = {}
    for s in spans:
        if s.parent_id:
            children.setdefault((s.trace_id, s.parent_id), []).append(s)
    return children


def self_intervals(span, children) -> list[tuple[float, float]]:
    """`span`'s interval minus the union of its children's (clipped)."""
    covered = merge([(max(c.start, span.start), min(c.end, span.end))
                     for c in children.get((span.trace_id, span.span_id), ())
                     if c.end > span.start and c.start < span.end])
    out, at = [], span.start
    for a, b in covered:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if span.end > at:
        out.append((at, span.end))
    return out


def wave_self_seconds(run, spans, names) -> list[float]:
    """Per complete wave of the window: the measure of the UNION, over the
    wave's duty traces, of the self intervals of the spans called one of
    `names` (a stage shared by several traces counts once)."""
    children = by_parent(spans)
    values = []
    for w in run.waves():
        if w["last_done"] is None:
            continue
        traces = {s.trace_id for s in spans if s.attrs.get("slot") == w["slot"]}
        mine = [s for s in spans if s.trace_id in traces and s.name in names]
        if not mine:
            continue
        union = merge([iv for s in mine for iv in self_intervals(s, children)])
        values.append(sum(b - a for a, b in union))
    return values


def cause_segments(spans, dues, starts, a: float, b: float):
    """[a, b) cut at every span boundary and trigger, each piece with its
    ONE cause: the cause nearest the device among the innermost open
    spans, of whatever kind of duty (none is assumed to be there: a duty
    that validator clients start has no fetch and no consensus span); with
    no span open, `pre_trigger` between a slot's start and its first
    trigger and `awaiting_input` after it. `dues` are the slots' first
    triggers, `starts` the same slots' starts."""
    live = [s for s in spans if s.end > a and s.start < b]
    before_due = list(zip(starts, dues))
    points = {a, b}
    for s in live:
        points.update(t for t in (s.start, s.end) if a < t < b)
    for pair in before_due:
        points.update(t for t in pair if a < t < b)
    points = sorted(points)
    out = []
    for lo, hi in zip(points, points[1:]):
        mid = (lo + hi) / 2
        open_ = [s for s in live if s.start <= mid < s.end]
        if not open_:
            before = any(start <= mid < due for start, due in before_due)
            out.append((lo, hi, NO_SPAN[0] if before else NO_SPAN[1]))
            continue
        parents = {(s.trace_id, s.parent_id) for s in open_}
        causes = {_CAUSE_OF.get(s.name, "other") for s in open_
                  if (s.trace_id, s.span_id) not in parents}
        out.append((lo, hi, next(c for c in ORDER if c in causes)))
    return out


_last: tuple = (None, None)  # (run, seconds by cause)


def idle_seconds(run) -> dict | None:
    """Device 0's idle seconds in the traced window, by cause: every idle
    instant gets exactly one, so the values sum to `window_s - busy_s`.
    Read from the window's duties of EVERY kind the run's mix names
    (`run.duty_types`).
    Worked out once a run (and noted on stderr, with the causes that are
    no metric): the five `idle_s.*` metrics read one answer."""
    global _last
    if _last[0] == id(run):
        return _last[1]
    recorded, trace = node_spans(), run.trace
    if recorded is None or trace is None:
        return None
    spans = duty_spans(run, recorded, run.duty_types)
    t0 = trace.wall_start
    t1 = t0 + trace.window_s
    kept = {id(s) for s in spans}
    others = [s for s in recorded if id(s) not in kept and s.end > t0 and s.start < t1]
    longest = max(others, key=lambda s: min(s.end, t1) - max(s.start, t0), default=None)
    waves = [w for w in run.waves() if w["duties"]]
    segments = cause_segments(
        spans, [w["due"] for w in waves],
        [run.window[0] + (w["slot"] - run.slots[0]) * run.slot_duration for w in waves], t0, t1)
    total = dict.fromkeys(ORDER + NO_SPAN, 0.0)
    i = 0
    for a, b in sorted((t0 + a, t0 + b) for a, b in trace.idle_gaps()):
        # both lists are sorted and disjoint: one pass
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            lo, hi, cause = segments[j]
            total[cause] += min(b, hi) - max(a, lo)
            j += 1
    print(f"node spans: {len(recorded)} in the ring, {len(spans)} of the window's {'+'.join(run.duty_types)} "
          f"duties, {len(others)} of other duties open in the traced window"
          + (f" (longest {longest.name} of {longest.attrs.get('duty')}, "
             f"{longest.end - longest.start:.3f} s {longest.status})" if longest else "")
          + "; device idle seconds by cause: "
          + ", ".join(f"{c} {v:.6f}" for c, v in total.items())
          + f"; sum {sum(total.values()):.6f} of window - busy "
          f"{trace.window_s - trace.busy_s:.6f}", file=sys.stderr, flush=True)
    _last = (id(run), total)
    return total
