"""DKG crypto benchmark — BASELINE config 4: batched G1 scalar-muls.

FROST ceremony verification is dominated by [k]P over G1: every
(peer, validator, coefficient) commitment check is one scalar-mul
(charon_tpu/dkg/frost.py verify paths; ref: dkg/frost.go runs them one
kryptology call at a time per ceremony). Here the whole verification
wave runs as ONE device program via blsops.g1_scalar_mul_batch.

Prints ONE JSON line: {"metric": "dkg_g1_scalar_mul", "value": N,
"unit": "muls/sec", "vs_baseline": R, ...}. vs_baseline divides by the
HOST native C++ backend's single-threaded scalar-mul rate measured in
the same run (the herumi-role reference on this machine) — honest on
any host, no canned constant.

Batch ladder: BENCH_DKG_BATCHES (space-separated), default TPU profile
4096/1024/256 muls, CPU-fallback profile one blsops.bucket_lanes
bucket (compile cost on the 1-core VM; liveness datapoint, not the
headline).

Modes (ISSUE 20, device DKG story):

  --verify-wave   the ceremony-verification wave as frost.py runs it —
                  g1_gen_mul_batch (share LHS) + commitment_eval_batch
                  (Straus commitment RHS) — A/B against the SAME wave
                  through the python g1_mul host loop, same run, same
                  inputs, lane-exact correctness cross-check.
  --reshare       the dkg/reshare ceremony end to end over the
                  in-memory transport (validators/sec).
  --smoke         tiny verify-wave shapes + the gate: on an
                  accelerator the device wave must be >=
                  --assert-verify-ratio (default 5x) the python loop,
                  measured twice before concluding (bench_hostplane
                  idiom). On the XLA:CPU fallback the 5x target is
                  physically out of reach — limb-emulated point math
                  is slower than host bigints, the same reason
                  --crypto-plane-decode auto resolves to python on CPU
                  — so the gate degrades to the lane-exact
                  correctness assertion and the JSON line says so.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

WARMUP = 4
ITERS = 3

T0 = time.perf_counter()


def hb(msg: str) -> None:
    print(f"[dkg-bench +{time.perf_counter() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--verify-wave", action="store_true")
    p.add_argument("--reshare", action="store_true")
    p.add_argument("--smoke", action="store_true")
    p.add_argument(
        "--assert-verify-ratio",
        type=float,
        default=5.0,
        help="smoke gate: device wave must beat the python loop by this "
        "factor on an accelerator (0 disables)",
    )
    return p.parse_args(argv)


def main(args) -> None:
    from bench_common import init_jax

    jax = init_jax()
    platform = jax.devices()[0].platform
    if args.reshare:
        return _bench_reshare(args, platform)
    if args.smoke or args.verify_wave:
        return _bench_verify_wave(args, platform)
    from charon_tpu.ops.blsops import bucket_lanes

    if "BENCH_DKG_BATCHES" in os.environ:
        batches = [int(b) for b in os.environ["BENCH_DKG_BATCHES"].split()]
    elif platform != "cpu":
        batches = [4096, 1024, 256]
    else:
        # one engine shape bucket, not a hand-picked constant: the CPU
        # liveness datapoint measures a shape the jit-cache ladder
        # actually serves, and follows the ladder if it changes
        batches = [bucket_lanes(64)]
    hb(f"jax up, platform={platform}, batches={batches}")

    from charon_tpu.crypto.g1g2 import G1_GEN, g1_from_bytes, g1_mul
    from charon_tpu.crypto.fields import R as FR_ORDER
    from charon_tpu.ops.blsops import BlsEngine

    # Host workload: random base points from the native backend (the
    # same role herumi plays for the reference's DKG).
    rng = random.Random(2026)
    nmax = max(batches)
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        impl = NativeImpl()
        t = time.perf_counter()
        bases = [
            g1_from_bytes(
                impl.secret_to_public_key(
                    rng.randrange(1, FR_ORDER).to_bytes(32, "big")
                )
            )
            for _ in range(nmax)
        ]
        hb(f"native backend built {nmax} base points in {time.perf_counter() - t:.1f}s")

        # CPU denominator: native single-threaded [k]P rate
        t = time.perf_counter()
        n_ref = 32
        for i in range(n_ref):
            impl.secret_to_public_key(
                rng.randrange(1, FR_ORDER).to_bytes(32, "big")
            )
        cpu_rate = n_ref / (time.perf_counter() - t)
        hb(f"host native scalar-mul rate: {cpu_rate:.0f}/s")
    except Exception as e:  # pure-Python fallback keeps the line parseable
        hb(f"native backend unavailable ({e}); python fallback (slow)")
        bases = [g1_mul(G1_GEN, rng.randrange(1, FR_ORDER)) for _ in range(nmax)]
        cpu_rate = 0.0

    scalars = [rng.randrange(1, FR_ORDER) for _ in range(nmax)]
    engine = BlsEngine()

    engine.g1_scalar_mul_batch(bases[:WARMUP], scalars[:WARMUP])
    hb(f"warmup batch={WARMUP} done")

    batch = None
    for attempt in batches:
        try:
            t = time.perf_counter()
            engine.g1_scalar_mul_batch(bases[:attempt], scalars[:attempt])
            hb(f"batch={attempt} compile+run {time.perf_counter() - t:.1f}s")
            batch = attempt
            break
        except Exception as e:
            hb(f"batch={attempt} unusable ({type(e).__name__}: {str(e)[:100]})")
    if batch is None:
        raise RuntimeError("no batch size compiled successfully")

    times = []
    for i in range(ITERS):
        t = time.perf_counter()
        out = engine.g1_scalar_mul_batch(bases[:batch], scalars[:batch])
        times.append(time.perf_counter() - t)
        hb(f"iter {i}: {times[-1]:.3f}s")
    # spot-check one lane against the host oracle
    k = rng.randrange(batch)
    assert out[k] == g1_mul(bases[k], scalars[k]), "device result != host oracle"

    best = min(times)
    rate = batch / best
    hb(f"batch={batch} best {best:.3f}s -> {rate:.0f} muls/sec")
    out_line = {
        "metric": "dkg_g1_scalar_mul",
        "value": round(rate, 2),
        "unit": "muls/sec",
        "vs_baseline": round(rate / cpu_rate, 4) if cpu_rate else 0.0,
        "platform": platform,
        "batch": batch,
        "host_native_rate": round(cpu_rate, 2),
    }
    print(json.dumps(out_line))


def _metric_for(args) -> tuple[str, str]:
    if args.reshare:
        return ("dkg_reshare", "validators/sec")
    if args.smoke or args.verify_wave:
        return ("dkg_verify_wave", "lanes/sec")
    return ("dkg_g1_scalar_mul", "muls/sec")


def _wave_inputs(rng, lanes: int, t: int):
    """A synthetic verification wave: per lane one share scalar plus a
    t-coefficient commitment row (public points, host-built)."""
    from charon_tpu.crypto.fields import R as FR_ORDER
    from charon_tpu.crypto.g1g2 import G1_GEN, g1_mul

    shares = [rng.randrange(1, FR_ORDER) for _ in range(lanes)]
    rows = [
        [g1_mul(G1_GEN, rng.randrange(1, FR_ORDER)) for _ in range(t)]
        for _ in range(lanes)
    ]
    xs = [(i % 9) + 1 for i in range(lanes)]
    return shares, rows, xs


def _python_wave(shares, rows, xs):
    """The frost.py host path for the same wave: [s]G plus the
    sequential commitment Horner loop, single-threaded python bigints."""
    from charon_tpu.crypto.fields import R as FR_ORDER
    from charon_tpu.crypto.g1g2 import G1_GEN, g1_add, g1_mul

    lhs, rhs = [], []
    for s, row, x in zip(shares, rows, xs):
        lhs.append(g1_mul(G1_GEN, s))
        acc, xpow = None, 1
        for c in row:
            acc = g1_add(acc, g1_mul(c, xpow))
            xpow = xpow * x % FR_ORDER
        rhs.append(acc)
    return lhs, rhs


def _bench_verify_wave(args, platform: str) -> None:
    """Device ceremony-verification wave vs the python g1_mul loop —
    same inputs, same run, lane-exact cross-check."""
    from charon_tpu.ops.blsops import BlsEngine, bucket_lanes

    t = 3 if (args.smoke or platform == "cpu") else 5
    lanes = bucket_lanes(8 if args.smoke else (64 if platform == "cpu" else 1024))
    rng = random.Random(2026)
    shares, rows, xs = _wave_inputs(rng, lanes, t)
    hb(f"verify-wave: platform={platform} lanes={lanes} t={t}")

    engine = BlsEngine()

    def device_wave():
        return (
            engine.g1_gen_mul_batch(shares),
            engine.commitment_eval_batch(rows, xs, t),
        )

    tc = time.perf_counter()
    dev_lhs, dev_rhs = device_wave()
    hb(f"device wave compile+run {time.perf_counter() - tc:.1f}s")

    def best_of(fn, iters=ITERS):
        times = []
        for _ in range(iters):
            tt = time.perf_counter()
            fn()
            times.append(time.perf_counter() - tt)
        return min(times)

    dev_s = best_of(device_wave)
    tt = time.perf_counter()
    py_lhs, py_rhs = _python_wave(shares, rows, xs)
    py_s = time.perf_counter() - tt
    hb(f"device {dev_s:.3f}s, python {py_s:.3f}s for {lanes} lanes")

    # lane-exact correctness: the device wave IS the host wave
    assert dev_lhs == py_lhs, "device share LHS != python oracle"
    assert dev_rhs == py_rhs, "device commitment eval != python oracle"

    ratio = py_s / max(dev_s, 1e-9)
    want = args.assert_verify_ratio if args.smoke else 0.0
    gate = "off"
    if want and platform != "cpu":
        if ratio < want:
            hb(f"ratio {ratio:.2f}x < {want}x — re-measuring before concluding")
            dev_s = best_of(device_wave)
            tt = time.perf_counter()
            _python_wave(shares, rows, xs)
            py_s = time.perf_counter() - tt
            ratio = py_s / max(dev_s, 1e-9)
        gate = "pass" if ratio >= want else "FAIL"
    elif want:
        # XLA:CPU limb emulation cannot beat host bigints at point math
        # (the --crypto-plane-decode auto rationale); the CPU gate is
        # the lane-exact correctness assertion above
        gate = "cpu-correctness-only"

    out_line = {
        "metric": "dkg_verify_wave",
        "value": round(lanes / max(dev_s, 1e-9), 2),
        "unit": "lanes/sec",
        "vs_baseline": round(ratio, 4),
        "platform": platform,
        "lanes": lanes,
        "t": t,
        "python_rate": round(lanes / max(py_s, 1e-9), 2),
        "gate": gate,
    }
    if platform == "cpu":
        out_line["note"] = (
            "XLA:CPU correctness run, not a device number; "
            "5x gate applies on an accelerator"
        )
    print(json.dumps(out_line))
    if gate == "FAIL":
        print(
            f"# verify-wave gate: device {ratio:.2f}x python "
            f"(want >= {want}x)",
            file=sys.stderr,
        )
        sys.exit(1)


def _bench_reshare(args, platform: str) -> None:
    """The dkg/reshare ceremony end to end (rotation shape) over the
    in-memory transport: all validators lane-parallel, device engine on
    an accelerator, host path on the CPU fallback."""
    import asyncio

    from charon_tpu.crypto import shamir
    from charon_tpu.crypto.fields import R as FR_ORDER
    from charon_tpu.crypto.g1g2 import G1_GEN, g1_mul
    from charon_tpu.dkg import reshare

    n, t = 4, 3
    v = 2 if (args.smoke or platform == "cpu") else 16
    rng = random.Random(2026)
    shares_by_idx: dict[int, list[int]] = {}
    old_pubshares, group_pks = [], []
    for _ in range(v):
        secret = rng.randrange(1, FR_ORDER)
        sh = shamir.split(
            secret, n, t, rand=lambda: rng.randrange(1, FR_ORDER)
        )
        for i, s in sh.items():
            shares_by_idx.setdefault(i, []).append(s)
        old_pubshares.append({i: g1_mul(G1_GEN, s) for i, s in sh.items()})
        group_pks.append(g1_mul(G1_GEN, secret))
    cfg = reshare.ReshareConfig(
        old_indices=tuple(range(1, n + 1)),
        new_indices=tuple(range(1, n + 1)),
        t_old=t,
        t_new=t,
        num_validators=v,
    )
    engine = None
    if platform != "cpu":
        from charon_tpu.ops.blsops import BlsEngine

        engine = BlsEngine()
    hb(f"reshare: platform={platform} n={n} t={t} v={v} "
       f"engine={'device' if engine else 'host'}")

    def ceremony():
        net = reshare.MemReshareTransport(cfg.old_indices, timeout=60.0)

        async def run():
            return await asyncio.gather(
                *(
                    reshare.run_reshare_parallel(
                        net.participant(i),
                        i,
                        cfg,
                        old_pubshares,
                        group_pks,
                        share_secrets=shares_by_idx[i],
                        engine=engine,
                    )
                    for i in cfg.old_indices
                )
            )

        return asyncio.run(run())

    tc = time.perf_counter()
    results = ceremony()
    first_s = time.perf_counter() - tc
    hb(f"first ceremony {first_s:.1f}s")
    # one recovered secret sanity-checks the whole run
    rec = shamir.recover_secret(
        {j: results[j - 1][0].secret_share for j in range(1, t + 1)}
    )
    assert g1_mul(G1_GEN, rec) == group_pks[0], "reshare moved the group key"

    best = first_s
    for _ in range(0 if args.smoke else ITERS - 1):
        tc = time.perf_counter()
        ceremony()
        best = min(best, time.perf_counter() - tc)

    out_line = {
        "metric": "dkg_reshare",
        "value": round(v / max(best, 1e-9), 2),
        "unit": "validators/sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "kind": "rotate",
        "n": n,
        "t": t,
        "validators": v,
        "path": "device" if engine else "host",
    }
    if platform == "cpu":
        out_line["note"] = (
            "XLA:CPU run: host-path ceremony, not a device number"
        )
    print(json.dumps(out_line))


if __name__ == "__main__":
    _args = parse_args()
    try:
        main(_args)
    except SystemExit:
        raise
    except Exception as e:
        _m, _u = _metric_for(_args)
        print(
            json.dumps(
                {
                    "metric": _m,
                    "value": 0.0,
                    "unit": _u,
                    "vs_baseline": 0.0,
                    "error": f"{type(e).__name__}: {e}"[:300],
                }
            )
        )
        # --smoke is a CI gate: a crashed or incorrect wave must fail
        # the tier, while plain bench modes stay parseable-line-exit-0
        sys.exit(1 if _args.smoke else 0)
