#!/usr/bin/env python3
"""Record the small device trace the reducer's test reads
(data/tiny.xplane.pb beside this file): two named jits, a few calls each, with idle
gaps between them. Run on the chip; prints the trace's structure."""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time
from pathlib import Path


def main() -> int:
    out = Path(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/tiny_trace")
    import jax
    import jax.numpy as jnp

    def verify_like(x):
        return jnp.tanh(x @ x).sum()

    def recombine_like(x):
        return (x * 3.0 + 1.0).sum()

    f = jax.jit(verify_like)
    g = jax.jit(recombine_like)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    tmp = out / "raw"
    shutil.rmtree(out, ignore_errors=True)
    tmp.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t_start = time.time_ns()
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    marks = []
    for i in range(3):
        t0 = time.time_ns()
        with jax.profiler.TraceAnnotation("bench_span", i=i):
            f(x).block_until_ready()
        t1 = time.time_ns()
        time.sleep(0.05)
        g(x).block_until_ready()
        t2 = time.time_ns()
        marks.append((t0, t1, t2))
        time.sleep(0.1)
    jax.profiler.stop_trace()
    t_stop = time.time_ns()
    pb = glob.glob(str(tmp / "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copyfile(pb, out / "tiny.xplane.pb")
    shutil.rmtree(tmp)
    data = jax.profiler.ProfileData.from_file(str(out / "tiny.xplane.pb"))
    info = {"bytes": os.path.getsize(out / "tiny.xplane.pb"), "t_start_ns": t_start,
            "t_stop_ns": t_stop, "marks": marks, "planes": []}
    for plane in data.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            p["lines"].append({
                "name": line.name, "events": len(evs),
                "first": [(e.name, e.start_ns, e.duration_ns) for e in evs[:4]],
            })
        info["planes"].append(p)
    (out / "tiny.json").write_text(json.dumps(info, indent=1))
    print(json.dumps(info)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
