"""Stage breakdown of the grouped-RLC verify kernel on the live device.

Times each stage of batched_verify_grouped_rlc as its own jitted program
(randomization MSMs / Miller+final-exp tail), for both the Pippenger MSM
path and the per-lane double-and-add path, plus the end-to-end kernel.
Guides kernel investment: the cost model says the randomization stage is
>99% of the arithmetic at batch 4096 — this verifies it on hardware.

Prints one JSON line per measurement to stdout (stderr heartbeats), e.g.
  {"stage": "g2_msm", "path": "pippenger", "batch": 4096, "secs": ...}
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

T0 = time.perf_counter()


def hb(msg: str) -> None:
    print(
        f"[breakdown +{time.perf_counter() - T0:6.1f}s] {msg}",
        file=sys.stderr,
        flush=True,
    )


def main() -> None:
    from bench_common import init_jax

    jax = init_jax()
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    batch = int(os.environ.get("BENCH_BREAKDOWN_BATCH", "4096"))
    hb(f"platform={platform} batch={batch}")

    from charon_tpu.crypto import h2c
    from charon_tpu.crypto.g1g2 import g1_from_bytes, g2_from_bytes
    from charon_tpu.ops import curve as C
    from charon_tpu.ops import limb
    from charon_tpu.ops import msm as MSM
    from charon_tpu.ops import pairing as DP
    from charon_tpu.tbls.native_impl import NativeImpl

    ctx, fr_ctx = limb.default_fp_ctx(), limb.default_fr_ctx()
    impl = NativeImpl()

    n_msgs = 8
    msgs_raw = [b"bench-msg-%d" % i for i in range(n_msgs)]
    msg_pts = [h2c.hash_to_g2(m) for m in msgs_raw]
    rng = random.Random(2026)
    sks = [rng.randrange(1, 2**250).to_bytes(32, "big") for _ in range(batch)]
    pks = [impl.secret_to_public_key(sk) for sk in sks]
    sigs = [impl.sign(sk, msgs_raw[i % n_msgs]) for i, sk in enumerate(sks)]
    hb("host workload built")

    m = n_msgs
    k = batch // m
    order = [j * n_msgs + g for g in range(m) for j in range(k)]
    g1f, g2f = C.g1_ops(ctx), C.g2_ops(ctx)
    pk_flat = C.g1_pack(ctx, [g1_from_bytes(pks[i]) for i in order])
    sig_flat = C.g2_pack(ctx, [g2_from_bytes(sigs[i]) for i in order])
    msg = C.g2_pack(ctx, msg_pts[:m])
    rand_flat = jnp.asarray(
        limb.ctx_pack(
            fr_ctx, [rng.randrange(1, 1 << 64) for _ in range(batch)]
        )
    )
    seg = jnp.repeat(jnp.arange(m, dtype=jnp.int32), k)
    hb("device arrays packed")

    def timed(name, path, fn, *args):
        f = jax.jit(fn)
        t = time.perf_counter()
        out = f(*args)
        jax.block_until_ready(out)
        compile_s = time.perf_counter() - t
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            jax.block_until_ready(f(*args))
            best = min(best, time.perf_counter() - t)
        hb(f"{name}/{path}: compile {compile_s:.1f}s steady {best:.3f}s")
        print(
            json.dumps(
                {
                    "stage": name,
                    "path": path,
                    "batch": batch,
                    "secs": round(best, 4),
                    "compile_secs": round(compile_s, 1),
                    "platform": platform,
                }
            ),
            flush=True,
        )

    # randomization stages, both paths
    timed(
        "g1_msm",
        "pippenger",
        lambda p, s: MSM.msm_segmented(
            g1f, fr_ctx, C.affine_to_point(g1f, p), s, seg, m, nbits=64
        ),
        pk_flat,
        rand_flat,
    )
    timed(
        "g2_msm",
        "pippenger",
        lambda p, s: MSM.msm(
            g2f, fr_ctx, C.affine_to_point(g2f, p), s, nbits=64
        ),
        sig_flat,
        rand_flat,
    )
    timed(
        "g1_msm",
        "per-lane",
        lambda p, s: C.point_scalar_mul(
            g1f, fr_ctx, C.affine_to_point(g1f, p), s, nbits=64
        ),
        pk_flat,
        rand_flat,
    )
    timed(
        "g2_msm",
        "per-lane",
        lambda p, s: C.point_scalar_mul(
            g2f, fr_ctx, C.affine_to_point(g2f, p), s, nbits=64
        ),
        sig_flat,
        rand_flat,
    )

    # fixed tail: M+1 Miller pairs + one final exp on prepacked lanes
    def tail(pkl, ql):
        f_lanes = DP.miller_loop(ctx, [(pkl, ql)])
        f_tot = DP._fp12_prod_tree(ctx, f_lanes)
        return DP.final_exp(ctx, f_tot)

    pk9 = C.g1_pack(ctx, [g1_from_bytes(pks[i]) for i in range(m + 1)])
    q9 = C.g2_pack(ctx, msg_pts[:m] + [h2c.hash_to_g2(b"tail")])
    timed("miller_tail", "shared", tail, pk9, q9)

    # end-to-end kernel, both paths
    def full(pk2, msg2, sig2, r2):
        return DP.batched_verify_grouped_rlc(ctx, fr_ctx, pk2, msg2, sig2, r2)

    pk_g = jax.tree_util.tree_map(lambda a: a.reshape(m, k, -1), pk_flat)
    sig_g = jax.tree_util.tree_map(lambda a: a.reshape(m, k, -1), sig_flat)
    rand_g = rand_flat.reshape(m, k, -1)
    for path, active in (("pippenger", True), ("per-lane", False)):
        MSM.set_msm(active)
        timed("full_verify", path, full, pk_g, msg, sig_g, rand_g)
    MSM.set_msm(None)


if __name__ == "__main__":
    main()
