#!/usr/bin/env python3
"""The `attest-forged` mix's control flow on the CPU: rehearse_nodedown.py's
run (host-only node, the crypto-plane service path patched in, the recorded
trace standing in for the profiler, --trace 1, wave hints passed through) on
the tests' 3-of-4 configuration with operator 2 forging one WELL-FORMED
partial in every set — and over a plane that has the two verify tiers of
`parallel/mesh.SlotCryptoPlane.verify_packed`: all lanes at once, and on a
failure every lane alone, each announced through `on_program`: `python
benchmark/tests/rehearse_forged.py [run.py's own options]`. Every wave's
verify flush then fails its first tier and is attributed; operator 2's set is
dropped whole and billed, the other three pass; every duty is made from
exactly t partials on share indices 1, 3, 4. (The plane's own parsed program
has answered per SET inside the first tier since PR 36, and the chip's cell
no longer reaches the second; the two-tier path rehearsed here is what a
failing segment of more than one set, and the unparsed twin, still take.)
After the run's last line it
prints ONE more stdout line, for the tests: the node's own spans, every
flush's FlushStats fields, what was billed to whom, every verify job's lanes
beside the answers it got, and every recombine row beside its aggregate."""

from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

CELL = "rehearsal-byz.attest-forged"


def make_root(tmp: Path) -> Path:
    """helpers.make_root's tiny 3-of-4 configuration under the benchmark's
    own `attest-forged` mix, reporting every per-layer metric the manifest
    lists for the chip's cell."""
    from benchmark.tests import helpers

    root = helpers.make_root(tmp, rehearsal=True)
    config = dict(helpers.REHEARSAL, name="rehearsal-byz")
    (root / "benchmark" / "configs" / "rehearsal-byz.json").write_text(json.dumps(config))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "rehearsal-byz", "source": config["source"],
        "file": "benchmark/configs/rehearsal-byz.json", "reduced": [], "why": "tests"})
    manifest["workloads"].append({
        "name": CELL, "config": "rehearsal-byz", "traffic": "attest-forged", "chips": 1,
        "why": "tests"})
    for m in manifest["per_layer"]:
        if "dv-3of4-1k-byz.attest-forged" in m["workloads"]:
            m["workloads"] = m["workloads"] + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def main(argv) -> int:
    from benchmark import run
    from benchmark.tests import attribution, helpers, planepatch
    from charon_tpu import tbls
    from charon_tpu.app import tracer
    from charon_tpu.crypto import g1g2

    verdicts: dict[bytes, bool] = {}  # signature -> what the process's tbls says

    class TieredPlane(planepatch.SleepPlane):
        """SleepPlane with the plane's two verify tiers. It still runs no
        program: a lane's verdict is the one the process's tbls (the C++
        engine) gave `Hinted.verify` for that signature."""

        on_program = None

        def pack_verify_inputs(self, pks, msgs, sigs):
            import numpy as np

            return ([verdicts[g1g2.g2_to_bytes(s)] for s in sigs],
                    np.ones(len(pks), dtype=bool))

        def _tier(self, family, n, answer):
            t0 = time.monotonic()
            time.sleep(self.device_s)
            if self.on_program is not None:
                self.on_program(f"mesh/{family}", time.monotonic() - t0, n)
            return answer

        def verify_packed(self, arrays, rand, n):
            sound = arrays[0]
            if self._tier("verify_rlc", n, all(sound)):
                return [True] * n
            return self._tier("verify", n, list(sound))

    class Hinted(planepatch.Checked):
        """Takes the wave hint and passes it on (rehearse_nodedown.py), and
        leaves the verdict to the plane's tiers."""

        wave_hints = True

        async def verify(self, items, deadline=None, wave=None):
            items = list(items)
            for (_pk, _root, sig), ok in zip(items, tbls.verify_batch(items)):
                verdicts[bytes(sig)] = ok
            return await self._tenant.verify(items, deadline=deadline, wave=wave)

    built = {}

    def host_plane(server):
        def handle(tenant):  # what the node's submitters will hold
            built["handle"] = attribution.Recorded(Hinted(tenant))
            return built["handle"]

        # planepatch's default windows of 50 / 200 ms are outlasted by a
        # loaded CPU's decode (a wave then splits, and only the flush holding
        # the forged set is attributed); the cell's configuration arms
        # 0.3 / 0.6 s. A whole wave closes its window at once either way.
        planepatch.host_plane(server, handle=handle, window=0.3, window_max=0.6,
                              plane=TieredPlane)
        built["run"], built["node"] = server.run, server.node

    helpers.fake_trace()
    with tempfile.TemporaryDirectory(prefix="bench_forged_") as tmp:
        args = ["--workload", CELL, "--seed", "3500000009", "--seconds", "9",
                "--trace", "1", *argv]
        try:
            code = run.main(args, root=make_root(Path(tmp)), exit_fn=sys.exit,
                            rehearsal=run.Rehearsal(cpu=True, patch=host_plane))
        except SystemExit as e:  # the run's own exit, its last line printed
            code = e.code
    fields = ("jobs", "lanes", "verify_jobs", "recombine_jobs", "sets_expected",
              "sets_seen", "sets_awaited", "window_closed_by", "attributed",
              "lanes_invalid", "sets_invalid", "attribute_span", "attribute_lanes")
    evidence = built["node"].sigagg.evidence
    print(json.dumps({
        "spans": [s for t in tracer.node_tracers().values() for s in t.dump()],
        "flushes": [{f: getattr(s, f) for f in fields}
                    for ts, s in built["run"].flushes if built["run"].in_window(ts)],
        "parsig_invalid": {str(i): evidence.count(i, "parsig_invalid") for i in (1, 2, 3, 4)},
        "sets": built["handle"].sets, "rows": built["handle"].rows,
    }, default=bytes.hex), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
