"""Headline benchmark: batched BLS12-381 signature verification throughput.

Prints ONE JSON line on stdout: {"metric", "value", "unit", "vs_baseline"}.
Progress heartbeats go to stderr so the driver sees liveness without
polluting the parseable output.

Metric matches BASELINE.json ("batched BLS verify sigs/sec"): the hot path
the reference executes one herumi C++ call at a time
(ref: core/validatorapi/validatorapi.go:1213 partial-sig verify,
core/parsigex/parsigex.go:94-98 peer-sig verify). Here a whole batch runs
as one XLA program on the accelerator.

Verification kernel: GROUPED random-linear-combination batch verification
(ops/pairing.py batched_verify_grouped_rlc) — lanes sharing a message
collapse into one Miller pair per distinct message (plus one aggregate
pair) under per-lane 64-bit random exponents, with ONE shared final
exponentiation (2^-64 soundness; on a False the caller re-runs the
per-lane kernel to attribute — exactly the strategy consensus clients
use for gossip batches, and the same message-sharing structure a DV
cluster sees: every validator in a committee signs the same attestation
data). The workload here is all-valid, so the batch must verify True.

Budget discipline (round-1 bench timed out, VERDICT Weak #1):
  * the workload is generated on host by the native C++ backend
    (milliseconds) — the device only runs the verify kernel;
  * ONE kernel is compiled per attempted batch size, after a tiny warmup
    batch; the persistent cache (.jax_cache, primed on this platform)
    makes the steady-state run seconds;
  * batch sizes sweep ASCENDING; a size whose program fails to compile
    is skipped;
  * every phase heartbeats with elapsed time.

vs_baseline: measured device throughput divided by the single-threaded
herumi-class CPU reference rate from BASELINE.md (the reference publishes
no numbers — BASELINE.json.published == {} — so we use the well-known
~1.5 ms/verify herumi envelope as the denominator; see BASELINE.md).
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

# Single-signature BLS verify on a modern CPU core with herumi/BLST-class
# C++ (the reference's backend): ~1.5 ms => ~666 sigs/sec.
CPU_REFERENCE_SIGS_PER_SEC = 666.0

WARMUP_BATCH = 4
ITERS = 3


def pick_batches(platform: str) -> list[int]:
    """Explicit BENCH_BATCHES always wins. Otherwise: the TPU profile
    sweeps real sizes ascending; a `JAX_PLATFORMS=cpu` run (correctness
    only — XLA:CPU compiles of the big pairing program take tens of
    minutes) runs one small shape."""
    if "BENCH_BATCHES" in os.environ:
        return [int(b) for b in os.environ["BENCH_BATCHES"].split()]
    if platform != "cpu":
        return [256, 1024, 4096]
    return [int(b) for b in os.environ.get("BENCH_BATCHES_CPU", "16").split()]

T0 = time.perf_counter()


def hb(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def main() -> None:
    from bench_common import init_jax

    jax = init_jax()
    platform = jax.devices()[0].platform
    batches = pick_batches(platform)
    hb(f"jax up, platform={platform}, devices={jax.devices()}, batches={batches}")

    from charon_tpu.crypto import h2c
    from charon_tpu.crypto.g1g2 import g1_from_bytes, g2_from_bytes
    from charon_tpu.ops import curve as C
    from charon_tpu.ops import limb
    from charon_tpu.ops import pairing as DP

    ctx = limb.default_fp_ctx()
    fr_ctx = limb.default_fr_ctx()
    hb(f"modules imported, ctx={ctx.name}")

    # Workload on host via the native C++ backend (ref-equivalent herumi
    # role). Distinct messages per lane come from a small message pool.
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        impl = NativeImpl()
    except Exception as e:  # pure-Python fallback (slower host setup)
        hb(f"native backend unavailable ({e}); python fallback")
        from charon_tpu.tbls.python_impl import PythonImpl

        impl = PythonImpl()

    n_msgs = 8
    msgs_raw = [b"bench-msg-%d" % i for i in range(n_msgs)]
    msg_pts = [h2c.hash_to_g2(m) for m in msgs_raw]

    rng = random.Random(2026)
    nmax = max(batches)
    sks = [rng.randrange(1, 2**250).to_bytes(32, "big") for _ in range(nmax)]
    pks = [impl.secret_to_public_key(sk) for sk in sks]
    sigs = [impl.sign(sk, msgs_raw[i % n_msgs]) for i, sk in enumerate(sks)]
    hb(f"host workload built: {nmax} keys/sigs")

    def pack(npack):
        """[M, K] grouped layout: lane i signed message i % n_msgs, so
        group m holds lanes m, m+n_msgs, m+2*n_msgs, ..."""
        import numpy as np

        m = min(n_msgs, npack)
        k = npack // m
        # lane index for group g, slot j is j*n_msgs + g in the original
        # round-robin assignment (sig[i] covers msgs_raw[i % n_msgs])
        order = [j * n_msgs + g for g in range(m) for j in range(k)]
        pk = C.g1_pack(ctx, [g1_from_bytes(pks[i]) for i in order])
        pk = jax.tree_util.tree_map(lambda a: a.reshape(m, k, -1), pk)
        sig = C.g2_pack(ctx, [g2_from_bytes(sigs[i]) for i in order])
        sig = jax.tree_util.tree_map(lambda a: a.reshape(m, k, -1), sig)
        msg = C.g2_pack(ctx, msg_pts[:m])
        rand = jax.numpy.asarray(
            np.asarray(
                limb.ctx_pack(
                    fr_ctx,
                    [rng.randrange(1, 1 << 64) for _ in range(m * k)],
                )
            ).reshape(m, k, -1)
        )
        return pk, msg, sig, rand

    def make_kernel():
        return jax.jit(
            lambda pk, msg, sig, r: DP.batched_verify_grouped_rlc(
                ctx, fr_ctx, pk, msg, sig, r
            )
        )

    # degradation ladder: fused-fp2 pallas -> plain mont pallas -> pure
    # XLA. Each rung re-jits once; a Mosaic regression in the newest
    # kernel family only costs its own speedup, not the whole fast path.
    from charon_tpu.ops import fptower as FT

    # BENCH_MXU=1: A/B the int8-MXU mont_mul decomposition
    # (ops/limb_mxu.py) — fp2 fusion off so every multiply actually
    # routes through the Toeplitz-matmul lowering
    bench_mxu = os.environ.get("BENCH_MXU") == "1"
    if bench_mxu and ctx.limb_bits != 12:
        # the decomposition only exists for the 12-bit geometry (the
        # CPU-fallback profile uses 24-bit limbs) — measuring here would
        # present the plain kernel as an MXU number
        hb(
            f"BENCH_MXU=1 ignored: ctx {ctx.name} has {ctx.limb_bits}-bit "
            "limbs, no MXU lowering"
        )
        bench_mxu = False
    if bench_mxu:
        hb("BENCH_MXU=1: int8-MXU mont_mul lowering active, fp2 fusion off")
        limb.set_mxu(True)
        FT.set_fp2_fusion(False)

    from charon_tpu.ops import msm as MSM

    def _rung_msm_off():
        MSM.set_msm(False)

    def _rung_fp2_off():
        FT.set_fp2_fusion(False)

    def _rung_pallas_off():
        limb.set_pallas(False)

    def _rung_mxu_off():
        limb.set_mxu(False)

    # under BENCH_MXU the fp2-fusion rung would rebuild a byte-identical
    # kernel (fusion is already off), but pallas-off stays meaningful:
    # once mxu steps down, mont_mul dispatches to the Pallas kernel and
    # a Mosaic regression there still needs the pure-XLA floor
    # "without msm" first: the Pippenger randomization stage is the
    # newest kernel family — a compiler regression there falls back to
    # the proven per-lane double-and-add (the round-4 1664 sigs/s path)
    # deploy-pinned env overrides (CHARON_MSM=0 etc., e.g. the TPU-watch
    # msm_off gate): the ops hot paths no longer read the environment,
    # so the baseline must re-assert them itself (core/autotune owns the
    # fold-in; absent vars resolve to None = kernel default)
    from charon_tpu.core.autotune import env_overrides

    _env_pins = env_overrides()

    def apply_baseline():
        """Restore the full fast path. Called before every batch attempt
        so a SIZE-induced failure (e.g. OOM at 16384) cannot burn rungs
        that then silently degrade the smaller batch's measurement."""
        MSM.set_msm(_env_pins.get("msm"))
        limb.set_pallas(None)
        if bench_mxu:
            limb.set_mxu(True)
            FT.set_fp2_fusion(False)
        else:
            limb.set_mxu(_env_pins.get("mxu_mont"))
            FT.set_fp2_fusion(True)

    def fresh_rungs():
        return (
            [
                ("without msm", _rung_msm_off),
                ("without mxu", _rung_mxu_off),
                ("without pallas", _rung_pallas_off),
            ]
            if bench_mxu
            else [
                ("without msm", _rung_msm_off),
                ("without fp2 fusion", _rung_fp2_off),
                ("without pallas", _rung_pallas_off),
            ]
        )

    state = {"kernel": make_kernel(), "rungs": fresh_rungs(), "used": []}

    def reset_ladder():
        apply_baseline()
        state["kernel"] = make_kernel()
        state["rungs"] = fresh_rungs()
        state["used"] = []

    def run_verify(args, label: str):
        """Run the kernel; on failure step down the degradation ladder
        and retry; re-raise once out of rungs so the caller can fall
        through to a smaller batch."""
        while True:
            try:
                t = time.perf_counter()
                ok = state["kernel"](*args)
                ok.block_until_ready()
                hb(f"{label} compile+run {time.perf_counter() - t:.1f}s")
                break
            except Exception as e:
                if not state["rungs"]:
                    raise
                rung_name, apply = state["rungs"].pop(0)
                hb(
                    f"{label} failed ({type(e).__name__}: {str(e)[:120]}); "
                    f"retrying {rung_name}"
                )
                apply()
                state["used"].append(rung_name)
                state["kernel"] = make_kernel()
        assert bool(ok), f"{label} batch verification failed"
        return ok

    def result_json(sigs_per_sec, batch, degraded, sweep):
        out = {
            "metric": "batched_bls_verify",
            "value": round(sigs_per_sec, 2),
            "unit": "sigs/sec",
            "vs_baseline": round(sigs_per_sec / CPU_REFERENCE_SIGS_PER_SEC, 4),
            "platform": platform,
            "batch": batch,
        }
        if degraded:
            # rungs burned while measuring THIS batch — the number is a
            # degraded-path measurement, never silently presented as the
            # full fast path
            out["degraded"] = degraded
        if len(sweep) > 1:
            out["sweep"] = {str(b): round(v, 2) for b, v in sweep.items()}
        return json.dumps(out)

    # tiny warmup shape first: proves the pipeline end-to-end before the
    # big compiles. TPU only — on a JAX_PLATFORMS=cpu run every shape is
    # a full extra pairing-program compile and the single small batch
    # needs no pipeline proof.
    if platform != "cpu":
        run_verify(pack(WARMUP_BATCH), f"warmup batch={WARMUP_BATCH}")

    best = None  # (sigs_per_sec, batch, degraded)
    sweep: dict[int, float] = {}
    for attempt in batches:
        try:
            # actual verified lane count: pack() lays lanes out [M, K]
            # with K = attempt // n_msgs, so a non-multiple batch would
            # otherwise silently verify fewer sigs than reported
            actual = min(n_msgs, attempt) * (attempt // min(n_msgs, attempt))
            reset_ladder()
            packed = pack(attempt)
            run_verify(packed, f"main batch={actual}")
            kernel = state["kernel"]
            times = []
            for i in range(ITERS):
                t = time.perf_counter()
                kernel(*packed).block_until_ready()
                times.append(time.perf_counter() - t)
                hb(f"batch={actual} iter {i}: {times[-1]:.3f}s")
            sigs_per_sec = actual / min(times)
            sweep[actual] = sigs_per_sec
            hb(
                f"batch={actual} best {min(times):.3f}s -> "
                f"{sigs_per_sec:.0f} sigs/sec"
            )
            if best is None or sigs_per_sec > best[0]:
                best = (sigs_per_sec, actual, list(state["used"]))
        except AssertionError:
            raise  # verification failing is a correctness bug, not size
        except Exception as e:
            hb(
                f"batch={attempt} unusable ({type(e).__name__}: "
                f"{str(e)[:100]}); continuing sweep"
            )
    if best is None:
        raise RuntimeError("no batch size compiled successfully")
    print(result_json(best[0], best[1], best[2], sweep))


if __name__ == "__main__":
    main()  # a failure raises: non-zero exit, no result line
