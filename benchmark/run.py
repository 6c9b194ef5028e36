#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one TPU chip, no CPU mode. Boots one real charon-tpu node and
its host-only peers, warms exactly the programs the cell's configuration
lists, serves `--seconds` of slots at the real slot cadence (the duties of the
kinds the cell's mix names), checks every broadcast aggregate against the
plain reference, and prints the
contract's result as the last stdout line. See README.md beside this file.
"""

from __future__ import annotations

T_PROCESS = __import__("time").time()  # process start, as near as Python gets

import argparse
import asyncio
import concurrent.futures
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import cachedir, manifest as manifestlib, traffic as trafficlib  # noqa: E402
from benchmark.watchdog import Watchdog, task_stacks  # noqa: E402

# Budgets, seconds. A warm run has to end inside the contract's 360 s; the
# cell's compiling run (no marker in the cache) inside its 1200 s.
WARM_TOTAL = 340.0
TRACED_TOTAL = 355.0  # ending the trace outlasts the window
COMPILING_TOTAL = 1150.0
PHASE = {
    "cluster": 60, "node": 60, "programs_warm": 240, "programs_compile": 900,
    "peers": 30, "warmup": 120, "align": 20, "drain": 15, "teardown": 30,
    "reference": 60, "trace_stop": 275,
}
# --trace 1: the wave of the window that runs under the profiler (an index
# into the window's slots), the program family whose end closes the trace,
# and the seconds that program's verdicts are held back once the end of the
# session has been started. The FIRST wave, to the end of its verify
# program, so that the session ends beside the rest of the window. The end
# is started from the plane's own hook, the instant the program returns, and
# the hold keeps the wave's next program off the device until the device
# tracer has stopped (it needs 15-19 ms; a recombine program dispatched
# inside them makes the trace 60 MB larger and its end 60 s longer: my chip
# runs, PRs 34-37, README.md "--trace 1"). tests/tracelab.py sets others.
TRACED_WAVE = 0
TRACE_UNTIL = "verify"
TRACE_HOLD = 0.025

@dataclasses.dataclass
class Rehearsal:
    """The way in for benchmark/tests, and for nothing else: `cpu` skips
    the look for a chip and takes a host-only node (no plane); `patch` is
    called with the Server once its node is built, before any program
    loads — the tests break the timed path there, and tests/control.py
    puts the control in the node's place."""

    cpu: bool = False
    patch: object = None  # callable(server) | None


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def device_info(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def routing(coalescer) -> dict:
    """The kernel routing in force (copied from chip_smoke.routing)."""
    from charon_tpu.ops import fptower, limb
    from charon_tpu.ops import msm as MSM

    ctx = coalescer.plane.ctx
    return {
        "limb_geometry": f"{ctx.n_limbs}x{ctx.limb_bits}b/{ctx.np_dtype.__name__}",
        "pallas": bool(limb._pallas_active(ctx)),
        "fp2_fusion": bool(fptower._FP2_FUSION and limb._pallas_active(ctx)),
        "msm": bool(MSM.msm_active()),
        "mxu": bool(limb._mxu_active(ctx)),
        "decode_rung": coalescer._decode_rung(),
    }


def watch_gc(spans: list) -> None:
    """Full collections as spans: a pause of the interpreter is host time
    no layer owns, and the harness can see it from outside."""
    started = {}

    def on_gc(phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            started["t"] = time.time()
        elif "t" in started:
            spans.append(("gc_gen2", started.pop("t"), time.time()))

    gc.callbacks.append(on_gc)


def emit(**obj) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


async def serve(args, cell, plan, wd, jax, cache, cache_log, events, dev,
                rehearsal: Rehearsal) -> dict:
    from benchmark import check, serve as servelib, tracered

    allowed = set(cell.config["programs"])
    server = servelib.Server(cell, plan, args.seed, wd, cache_log, events, allowed,
                             require_plane=not rehearsal.cpu)
    loop = asyncio.get_running_loop()
    wd.dumpers.append(lambda: task_stacks(loop))
    wd.dumpers.append(lambda: "open flushes: inflight=%s flushes=%s" % (
        getattr(getattr(server, "coalescer", None), "_inflight", "?"),
        getattr(getattr(server, "coalescer", None), "flushes", "?")))
    vers = versions()
    sources = cachedir.sources_hash(ROOT, cell.config_file)
    marker = cachedir.read_marker(cache, cell.name, vers, sources)
    warm = marker is not None
    if not warm:
        wd.extend_total(COMPILING_TOTAL)
    elif args.trace:
        wd.extend_total(TRACED_TOTAL)
    wd.note(f"cache at {cache}: marker for {cell.name} "
            + (f"found, {len(marker['keys'])} keys" if warm
               else "absent, or of other sources: this is the compiling run"))

    def plane_miss(module, key):
        reason = cachedir.miss_reason(cell.name, marker, server.in_window, module, key)
        if reason is not None:
            if marker is not None and not server.in_window:
                cachedir.drop_marker(cache, cell.name)
            wd.fail(reason, key=key)

    cache_log.on_plane_miss = plane_miss

    # Tracing and lowering two pairing programs allocates tens of millions
    # of objects; the cyclic collector re-walks the growing heap all the
    # way. Off for the set-up, back on (after one full collection) before
    # the slot clock is joined: the window runs under the default policy.
    gc.disable()
    with wd.phase("cluster", PHASE["cluster"]):
        await asyncio.to_thread(server.make_cluster)
    with wd.phase("node", PHASE["node"]):
        await server.build_node()
        if rehearsal.patch is not None:
            rehearsal.patch(server)
    # only the CPU rehearsal (host-only node, no plane) skips the plane's phases
    has_plane = server.coalescer is not None
    route, programs = (routing(server.coalescer) if has_plane else {}), []
    if has_plane:
        with wd.phase("programs", PHASE["programs_warm" if warm else "programs_compile"]):
            programs = await asyncio.to_thread(server.load_programs, warm)
            server.hook_plane()
    with wd.phase("peers", PHASE["peers"]):
        await server.start_peers()
    if has_plane:
        with wd.phase("warmup", PHASE["warmup"]):
            warmup = await server.await_warmup()
        # the key-table warm-up ran g1dec on the lifecycle's thread
        g1 = [(m, k) for m, k, _t in cache_log.misses if cachedir.is_plane(m)]
        programs.append({
            "program": next(p for p in cell.config["programs"] if p.startswith("g1dec@")),
            "seconds": warmup.get("seconds"),
            "cache": "miss" if len(g1) > len(programs) else "hit",
        })
    if has_plane and not warm:
        # every plane program this set-up compiled has to be in the cache
        # now: then, and only then, later runs of this checkout are warm
        stored = {k for _m, k in cache_log.written}
        compiled = {k for m, k, _t in cache_log.misses if cachedir.is_plane(m)}
        if compiled and compiled <= stored:
            cachedir.write_marker(cache, cell.name, vers, sources, sorted(stored))
            wd.note(f"marker written: {len(stored)} plane programs stored")
        else:
            wd.note(f"NO marker: compiled {sorted(compiled)}, stored {sorted(stored)}")

    with wd.phase("collect", 60):
        gc.enable()
        gc.collect()
    slots = int(round(args.seconds / plan.slot_duration))
    with wd.phase("align", PHASE["align"]):
        start = server.open_window(slots)
        setup_s = start - T_PROCESS
        await asyncio.sleep(max(0.0, start - time.time()))
    run = server.run
    run.setup_s = setup_s
    watch_gc(run.spans)
    server.in_window = True
    requests_before = events.total_requests
    # --trace 1: ONE wave of the window runs under the profiler (TRACED_WAVE),
    # from its slot's start until the program TRACE_UNTIL names has ended.
    # The trace ends on a thread of its own beside what is left of the
    # window, the teardown and the reference (what ending it costs, and
    # why: README.md "--trace 1"), and is awaited last.
    trace_handle = trace_future = None
    trace_lock = threading.Lock()
    traced_k = TRACED_WAVE % slots

    def end_trace(hold: float = 0.0) -> None:
        """Start the end of the session, once, from whichever thread asks."""
        nonlocal trace_future
        with trace_lock:
            if trace_handle is None or trace_future is not None:
                return
            trace_future = future = concurrent.futures.Future()
        trace_handle["stop_from"], trace_handle["stop_wall"] = time.monotonic(), time.time()

        def work():
            try:
                future.set_result(tracered.stop(trace_handle, wd.note))
            except BaseException as e:  # noqa: BLE001 — handed to the awaiting loop
                future.set_exception(e)

        threading.Thread(target=work, name="bench-trace-stop", daemon=True).start()
        if hold:
            time.sleep(hold)

    def program_ended(family: str, at: float) -> None:
        # on the plane's dispatch thread, before the program's caller has its result
        if (trace_handle is not None and trace_future is None
                and family.startswith(TRACE_UNTIL) and at >= trace_handle["wall"]):
            end_trace(TRACE_HOLD)

    if args.trace:
        server.on_program_end = program_ended
    for k, slot in enumerate(run.slots):
        end = start + (k + 1) * plan.slot_duration
        if args.trace and k == traced_k:
            trace_handle = tracered.start(jax)
            run.traced_slot = slot
        with wd.phase(f"slot {k + 1}/{slots} (slot {slot})", plan.slot_duration + 3):
            await asyncio.sleep(max(0.0, end - time.time()))
        if trace_handle is not None and trace_future is None:  # no such program came
            end_trace()
    with wd.phase("drain", PHASE["drain"]):
        # a duty not at the beacon by the end of its own slot has FAILED;
        # these seconds only tell a late aggregate (compared like any
        # other, given its real latency) from one that never came
        until = time.time() + 10.0
        while time.time() < until and any(d.done is None for d in run.duties):
            await asyncio.sleep(0.05)
        run.gave_up = time.time()
    server.in_window = False
    compiles_in_window = events.total_requests - requests_before

    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use") or 0)
    counters = servelib.device_counters(server) if has_plane else {"events": {}, "info": {}}
    rejected = server.node.sigagg.evidence.count(plan.fault.operator, "parsig_invalid") \
        if plan.fault.operator else 0
    expected_forged = server.expected_forged_sets()
    with wd.phase("teardown", PHASE["teardown"]):
        late = await server.teardown()
    if late:
        wd.note(f"teardown left behind: {late}")

    fork = server.cluster.lock.fork_info()  # the chain's parameters, as the lock states them
    with wd.phase("reference", PHASE["reference"]):
        t0 = time.monotonic()
        checks = await asyncio.to_thread(
            check.compare, run, server.cluster, plan,
            (bytes(fork.fork_version), bytes(fork.genesis_validators_root)),
            counters["events"], rejected, expected_forged, compiles_in_window)
        reference_s = time.monotonic() - t0
    trace_lead_s = None
    if trace_future is not None:
        # the budget is the stop's own, from the second it began: what is
        # left of it here is what the run may still wait
        left = trace_handle["stop_from"] + PHASE["trace_stop"] - time.monotonic()
        with wd.phase("trace_stop", max(1.0, left)):
            run.trace = await asyncio.wrap_future(trace_future)
        # the next program's dispatch after the end was started: under
        # ~0.02 s it rides into the trace (README.md "--trace 1")
        later = [t - sec for _f, sec, _l, t in run.programs if t - sec > trace_handle["stop_wall"]]
        trace_lead_s = round(min(later) - trace_handle["stop_wall"], 4) if later else None
    run_wall_s = time.time() - T_PROCESS
    budget = {  # how near its budget the run came (README.md "--trace 1")
        "run_wall_s": round(run_wall_s, 2), "window_opened_s": round(setup_s, 2),
        "traced_tail_s": round(run_wall_s - setup_s, 2) if args.trace else None,
        "stop_trace_s": getattr(run.trace, "stop_s", None),
        "trace_stop_lead_s": trace_lead_s,
        "trace_events": getattr(run.trace, "events", None),
        "trace_bytes": getattr(run.trace, "bytes", None),
        "trace_planes": getattr(run.trace, "planes", None),
    }
    wd.note("budget: " + ", ".join(f"{k} {v}" for k, v in budget.items()))
    vc_spans = {name for kind in plan.kinds for name in kind.VC_SPANS}
    info = {
        "cell": cell.name, "seed": args.seed, "device": dev, "versions": vers,
        "routing": route, "cache_dir": str(cache), "cache_marker": warm,
        "programs": programs,
        "stored_in_setup": sorted(k[:24] for _m, k in cache_log.written),
        "plane_cache": {"hits": len([1 for m, _k, _t in cache_log.hits
                                     if cachedir.is_plane(m)]),
                        "misses": len([1 for m, _k, _t in cache_log.misses
                                       if cachedir.is_plane(m)])},
        "program_seconds": [[e, n, round(s, 2)] for e, n, s, _t in events.durations],
        "duty_sample": len(run.duties), "slots": run.slots, "traced_slot": run.traced_slot,
        "waves": [
            {"slot": w["slot"], "duties": w["duties"],
             "trigger_to_last_broadcast_s": (
                 round(w["last_done"] - w["due"], 3) if w["last_done"] else None)}
            for w in run.waves()],
        "flushes": [
            {"done_at_s": round(ts - run.window[0], 3), "lanes": f.lanes, "jobs": f.jobs,
             "device_s": round(f.flush_seconds, 4), "window_s": round(f.window, 3),
             "decode_s": round(sum(b - a for a, b in f.decode_spans), 4),
             "decode_from_s": round(min(a for a, _b in f.decode_spans) - run.window[0], 3)
             if f.decode_spans else None,
             "pack_s": round(f.pack_span[1] - f.pack_span[0], 4) if f.pack_span else None}
            for ts, f in run.flushes if run.in_window(ts)],
        "qbft_decided_at_s": sorted({round(a - run.window[0], 2) for n, a, _b in run.spans
                                     if n == "qbft_decided"}),
        "vc_spans_s": [[n, round(a - run.window[0], 3), round(b - a, 3)]
                       for n, a, b in run.spans if n in vc_spans],
        "gc_pauses_s": [round(b - a, 3) for n, a, b in run.spans
                        if n == "gc_gen2" and run.in_window(a)],
        "compiles_in_window": compiles_in_window,
        "forged_sets": {"sent": sum(p.forged_sets for p in server.peers),
                        "rejected": rejected},
        "counters": counters["info"], "reference_seconds": round(reference_s, 3),
        "phases": {n: round(t, 2) for n, _s, t in wd.phases},
        **budget,
        "patched": getattr(rehearsal.patch, "__name__", None),
    }
    return {"run": run, "checks": checks, "info": info}


def result_line(cell, run, checks, dev, trace: bool, root: Path, manifest) -> dict:
    from benchmark import check

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        read = manifestlib.load_reader(root, manifest, m.reader)
        value = read(run, **m.params)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    # a duty has until the end of its own slot: 2/3 slot after its trigger
    failed = sum(1 for d in run.duties
                 if d.done is None or d.done > d.due + run.slot_duration * 2 / 3)
    line = {
        "correct": check.verdict(checks),
        "attempted": len(run.duties),
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if trace and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown(run)
    line["checks"] = checks  # each number compared beside its limit: last
    return line


def main(argv=None, root: Path = ROOT, exit_fn=os._exit,
         rehearsal: Rehearsal | None = None) -> int:
    """`root` holds BENCHMARK.json and the data files (the tests pass a
    copy); `exit_fn` is how the run ends once its last line is out;
    `rehearsal` is the tests' (see Rehearsal)."""
    rehearsal = rehearsal or Rehearsal()
    args = parse_args(argv if argv is not None else sys.argv[1:])
    wd = Watchdog(WARM_TOTAL, exit_fn=exit_fn)
    try:
        manifest = manifestlib.load_manifest(root)
        cell = manifestlib.load_cell(root, args.workload, manifest)
        plan = trafficlib.make_plan(cell.config, cell.traffic, args.seed,
                                    manifestlib.bench_dir(root, manifest))
        trafficlib.check_programs(plan, cell.config)
        slots = args.seconds / plan.slot_duration
        if slots < 1 or abs(slots - round(slots)) > 1e-9:
            raise trafficlib.TrafficError(
                f"--seconds {args.seconds:g} is not a whole number of "
                f"{plan.slot_duration:g} s slots")
    except (manifestlib.ManifestError, trafficlib.TrafficError, KeyError, StopIteration) as e:
        wd.fail(f"before boot: {type(e).__name__}: {e}")
        return 2

    for key, value in cell.config.get("env", {}).items():
        os.environ[key] = value
    if rehearsal.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    cache, named = cachedir.configure(root)
    # what the configuration says it needs of the program, before anything
    # boots or compiles (a directory without the program says so below)
    requires = cell.config.get("requires", ())
    if requires and importlib.util.find_spec("charon_tpu") is not None:
        missing = manifestlib.unresolved(requires)
        if missing:
            wd.fail(f"before boot: the configuration requires {missing} of the program, "
                    f"which this tree does not have")
            return 2
    with wd.phase("import jax", 60):
        import jax

        cache_log, events = cachedir.configure_jax(jax)
        try:
            dev = device_info(jax)
        except RuntimeError as e:
            wd.fail(f"jax found no device: {e}")
            return 2
    dev["memory_peak_bytes"] = 0
    wd.device = dev
    if not rehearsal.cpu and (dev["platform"] != "tpu" or dev["count"] < cell.chips):
        # no CPU mode: a measurement path that finds no chip fails
        wd.note(f"need {cell.chips} TPU chip(s), found {dev}")
        print(f"error: need {cell.chips} TPU chip(s), found {dev}", file=sys.stderr)
        wd.close()
        return 2
    try:
        from charon_tpu.core import autotune
    except ImportError as e:  # a directory that holds only the benchmark
        print(f"error: the program is not here: {e}", file=sys.stderr)
        wd.close()
        return 2

    autotune.apply_env()  # the configuration's env pins (CHARON_MSM)
    wd.note(f"cell {cell.name} seed {args.seed}: cache {cache}"
            + (f" (the machine named {named}: not used)" if named else ""))

    try:
        out = asyncio.run(serve(args, cell, plan, wd, jax, cache, cache_log, events, dev,
                                rehearsal))
    except Exception as e:  # noqa: BLE001 — the boundary: report and fail
        import traceback

        traceback.print_exc(file=sys.stderr)
        wd.fail(f"{type(e).__name__}: {e}")
        return 3
    run, checks = out["run"], out["checks"]
    emit(info=out["info"])
    line = result_line(cell, run, checks, dev, bool(args.trace), root, manifest)
    from benchmark import check

    print(check.report(checks), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    wd.close()
    sys.stdout.flush()
    sys.stderr.flush()
    # threads of the node (executors, p2p) may not keep the process alive
    exit_fn(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
