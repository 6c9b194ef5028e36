#!/usr/bin/env python3
"""python3 benchmark/tests/tracelab.py --design A|B|C [--lab 1] --workload ... (run.py's arguments)

On the chip: what ending a traced run's profiler session costs, by WHICH wave
is traced and by what the device runs beside the end. A whole run of run.py
under one of three designs —

  A  the window's FIRST wave, to the end of its verify program (run.py's
     own): the wave's recombine program and the two later waves are
     dispatched while the session ends;
  B  the LAST wave, to the end of its verify program: the recombine program
     alone is dispatched beside the end;
  C  the LAST wave, to the end of its recombine program: nothing is
     dispatched beside the end, and the trace holds both programs

(call `p37f`, PR 37, ran them when the end was still started from the loop's
20 ms poll with no hold: A 70.3 s, B 69.1 / 74.0 s, C 125.8 / 166.5 s and two
runs dead at 355 s — PERF.md §6)

— and, with `--lab 1`, the same programs traced again in the SAME process
once the run has printed its line and its node is gone: one dispatch each
through the plane's own prewarm entries, a fresh session per variant, the
end of the session timed with and without programs dispatched beside it.
Each variant prints one `LAB {...}` line on stdout AFTER the run's last line
(so this is no benchmark run: the driver never calls it)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

DESIGNS = {"A": (0, "verify"), "B": (-1, "verify"), "C": (-1, "step")}


def cycle(jax, name, body) -> dict:
    """One session: `body(stop)` dispatches inside it and calls `stop()`
    (on whichever thread) exactly once; what the end took and held."""
    from benchmark import tracered

    out = {"variant": name}
    handle = tracered.start(jax)
    box = {}

    def stop():
        t0 = time.time()
        box["blob"] = tracered.stop_bytes(handle)
        box["stop_s"] = time.time() - t0
        box["window_s"] = t0 - handle["wall"]

    t0 = time.time()
    try:
        out["marks_s"] = body(stop)
        out["cycle_s"] = round(time.time() - t0, 2)
        blob = box["blob"]
        out["stop_s"], out["bytes"] = round(box["stop_s"], 2), len(blob)
        t1 = time.time()
        summary = tracered.reduce_bytes(blob, handle["wall"], box["window_s"])
        out["reduce_s"] = round(time.time() - t1, 2)
        out["events"], out["planes"] = summary.events, summary.planes
        out["modules"] = [[n[:40], round(s, 4), round(d, 5)] for n, s, d in summary.modules]
        out["busy_s"] = summary.busy_s
    except Exception as e:  # noqa: BLE001 — a lab: say it and go on
        out["error"] = f"{type(e).__name__}: {e}"
    print("LAB " + json.dumps(out), flush=True)
    return out


def lab(server, jax) -> None:
    plane = server.coalescer.plane
    want = {}
    for item in server.cell.config["programs"]:
        family, bucket = item.split("@")
        if family != "g1dec":
            want[family] = int(bucket)
    entries = plane.prewarm_programs(
        verify_lanes=tuple(b for f, b in want.items() if f.startswith("verify")),
        recombine_lanes=tuple(b for f, b in want.items() if f.startswith("step")),
        decompress=True)
    fns = {family: fn for _k, family, bucket, fn in entries if want.get(family) == bucket}
    verify = next(fn for f, fn in fns.items() if f.startswith("verify"))
    step = next(fn for f, fn in fns.items() if f.startswith("step"))

    def timed(fn):
        t0 = time.time()
        fn()
        return round(time.time() - t0, 3)

    def beside(stop, then):
        """The end on a thread of its own, `then` on this one beside it."""
        th = threading.Thread(target=stop)
        th.start()
        marks = then()
        th.join()
        return marks

    def verify_stop(stop):
        m = [timed(verify)]
        stop()
        return m

    def idle5_verify_stop(stop):
        time.sleep(5.0)
        return verify_stop(stop)

    def verify_stop_beside_step(stop):
        m = [timed(verify)]
        return m + beside(stop, lambda: [time.sleep(0.03), timed(step)][1:])

    def verify_stop_beside_three_waves(stop):
        m = [timed(verify)]

        def waves():
            time.sleep(0.03)
            got = [timed(step)]
            for _ in range(2):
                time.sleep(2.0)
                got += [timed(verify), timed(step)]
            return got

        return m + beside(stop, waves)

    def verify_step_stop(stop):
        m = [timed(verify), timed(step)]
        stop()
        return m

    for name, body in (
        ("verify_stop", verify_stop),
        ("verify_step_stop", verify_step_stop),
        ("verify_stop_beside_step", verify_stop_beside_step),
        ("verify_stop_beside_three_waves", verify_stop_beside_three_waves),
        ("idle5_verify_stop", idle5_verify_stop),
        ("verify_stop.again", verify_stop),
    ):
        cycle(jax, name, body)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--design", choices=sorted(DESIGNS), required=True)
    ap.add_argument("--lab", type=int, choices=(0, 1), default=0)
    args, rest = ap.parse_known_args()
    from benchmark import run

    run.TRACED_WAVE, run.TRACE_UNTIL = DESIGNS[args.design]
    if not args.lab:
        return run.main(rest)
    held = {}

    def keep(server):  # run.Rehearsal's way in: nothing is patched
        held["server"] = server

    def go_on(code):
        if code:
            os._exit(code)

    rc = run.main(rest, exit_fn=go_on, rehearsal=run.Rehearsal(patch=keep))
    if rc == 0 and "server" in held:
        import jax

        lab(held["server"], jax)
    sys.stdout.flush()
    os._exit(rc)


if __name__ == "__main__":
    sys.exit(main())
