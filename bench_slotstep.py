"""Slot-step scale benchmark: BASELINE.json configs 2/3 on real hardware.

Measures the framework's "training step": one SlotCryptoPlane step for V
validators with t-of-n partial signatures — Lagrange recombination [V]
and the group-signature verify [V], one pairing lane a validator (the
partials are verified where they enter a node, by the verify programs) —
as a single compiled program on the device (ref equivalent:
core/sigagg/sigagg.go:84-122, executed per-signature on CPU).

Prints one JSON line per measured config to stdout, plus an extrapolation
to the 100k-validator north star (BASELINE config 5). Heartbeats on
stderr. Run: python bench_slotstep.py [V t [V t ...]]
Env: SLOTSTEP_CONFIGS="64:4 256:4" overrides the config list.
"""

from __future__ import annotations

import json
import os
import sys
import time

T0 = time.perf_counter()


def hb(msg: str) -> None:
    print(f"[slotstep +{time.perf_counter() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def main() -> None:
    from bench_common import init_jax

    jax = init_jax()
    platform = jax.devices()[0].platform
    hb(f"platform={platform} devices={jax.devices()}")
    if platform == "cpu" and "SLOTSTEP_CONFIGS" not in os.environ and len(sys.argv) == 1:
        # JAX_PLATFORMS=cpu correctness run: one tiny shape
        os.environ["SLOTSTEP_CONFIGS"] = "8:3"

    from charon_tpu.crypto import h2c
    from charon_tpu.crypto.g1g2 import g1_from_bytes, g2_from_bytes
    from charon_tpu.parallel import SlotCryptoPlane, make_mesh
    from charon_tpu.tbls.native_impl import NativeImpl

    if len(sys.argv) > 1:
        raw = list(zip(sys.argv[1::2], sys.argv[2::2]))
    else:
        # defaults are the BASELINE.json workload shapes: config 2
        # (1k-validator attestation duty, 4-of-7) and config 3
        # (sync contribution, 512 validators x 7 partials); the 100k
        # mega-operator (config 5) extrapolates from the largest
        raw = [
            pair.split(":")
            for pair in os.environ.get(
                "SLOTSTEP_CONFIGS", "256:4 512:7 1024:4"
            ).split()
        ]
    configs = [(int(v), int(t)) for v, t in raw]
    vmax = max(v for v, _ in configs)
    tmax = max(t for _, t in configs)

    impl = NativeImpl()
    hb("generating workload on host (native backend)")
    import random

    rng = random.Random(2026)
    n_msgs = 8
    msg_pool = [h2c.hash_to_g2(b"slot-%d" % i) for i in range(n_msgs)]

    pubshares, msgs, partials, group_pks, indices = [], [], [], [], []
    for v in range(vmax):
        sk = rng.randrange(1, 2**250).to_bytes(32, "big")
        shares = impl.threshold_split(sk, tmax + 1, tmax)
        msg_raw = b"slot-%d" % (v % n_msgs)
        idx = sorted(shares)[:tmax]
        pubshares.append(
            [g1_from_bytes(impl.secret_to_public_key(shares[i])) for i in idx]
        )
        partials.append(
            [g2_from_bytes(impl.sign(shares[i], msg_raw)) for i in idx]
        )
        msgs.append(msg_pool[v % n_msgs])
        group_pks.append(g1_from_bytes(impl.secret_to_public_key(sk)))
        indices.append(idx)
    hb(f"workload ready: {vmax} validators x {tmax} shares")

    mesh = make_mesh(jax.devices()[:1])
    results = []
    for v, t in configs:
        plane = SlotCryptoPlane(mesh, t=t)
        args = plane.pack_inputs(
            [row[:t] for row in pubshares[:v]],
            msgs[:v],
            [row[:t] for row in partials[:v]],
            group_pks[:v],
            [row[:t] for row in indices[:v]],
        )
        rand = plane.make_rand(v, rng=random.Random(7))
        ts = time.perf_counter()
        _, all_ok = plane.step_rlc(*args, rand)
        all_ok.block_until_ready()
        hb(
            f"V={v} t={t} compile+run {time.perf_counter() - ts:.1f}s "
            f"all_ok={bool(all_ok)}"
        )
        assert bool(all_ok), f"slot step failed at V={v}"
        times = []
        for _ in range(3):
            ts = time.perf_counter()
            plane.step_rlc(*args, rand)[1].block_until_ready()
            times.append(time.perf_counter() - ts)
        best = min(times)
        per_slot = best
        results.append(
            {
                "metric": "slot_step",
                "validators": v,
                "threshold": t,
                "value": round(v / best, 2),
                "unit": "validators/sec",
                "slot_time_s": round(per_slot, 4),
                "fits_12s_slot": per_slot < 12.0,
                "platform": platform,
            }
        )
        hb(f"V={v} steady {best:.3f}s -> {v / best:.0f} validators/sec")

    for r in results:
        print(json.dumps(r))
    # extrapolate the 100k north star from the largest measured config
    big = results[-1]
    rate = big["value"]
    secs = 100_000 / rate
    import math

    extrap = {
        "metric": "slot_step_extrapolated_100k",
        "value": round(secs, 2),
        "unit": "seconds/slot",
        "basis": f"linear from V={big['validators']} rate",
        "fits_12s_slot": secs < 12.0,
        # the config-5 statement: the validator axis shards linearly
        # over the mesh (parallel/mesh.py), so N devices at the measured
        # single-device rate R close the 12 s slot budget
        "devices_needed_for_12s_slot": max(1, math.ceil(secs / 12.0)),
        "per_device_rate": rate,
        "platform": platform,
    }
    print(json.dumps(extrap))


if __name__ == "__main__":
    main()
