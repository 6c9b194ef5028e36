#!/usr/bin/env python3
"""Record the small device trace the reducer's test reads
(data/tiny.xplane.pb beside this file): two named jits, three calls each, with idle
gaps between them and a longer one at the end, under the options and through the session of
benchmark/tracered.py, as a traced run records its window. Run on the chip;
prints the trace's structure and what ending it took."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main() -> int:
    out = Path(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/tiny_trace")
    import jax
    import jax.numpy as jnp

    from benchmark import tracered

    def verify_like(x):
        return jnp.tanh(x @ x).sum()

    def recombine_like(x):
        return (x * 3.0 + 1.0).sum()

    f = jax.jit(verify_like)
    g = jax.jit(recombine_like)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    out.mkdir(parents=True, exist_ok=True)
    handle = tracered.start(jax)
    marks = []
    for _ in range(3):
        t0 = time.time()
        f(x).block_until_ready()
        t1 = time.time()
        time.sleep(0.05)
        g(x).block_until_ready()
        t2 = time.time()
        marks.append([t - handle["wall"] for t in (t0, t1, t2)])
        time.sleep(0.1)
    time.sleep(0.3)  # the longest idle gap is the one after the last call
    stopped = time.time()
    blob = tracered.stop_bytes(handle)
    stop_s = time.time() - stopped
    (out / "tiny.xplane.pb").write_bytes(blob)
    data = jax.profiler.ProfileData.from_file(str(out / "tiny.xplane.pb"))
    info = {"bytes": len(blob), "window_s": stopped - handle["wall"], "stop_s": stop_s,
            "marks_s": marks, "planes": []}
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
