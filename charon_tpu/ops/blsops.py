"""User-facing batched BLS kernels: verify, threshold-aggregate, aggregate.

This is the device engine behind the tbls TPU implementation. Where the
reference recombines one signature at a time on the CPU
(ref: tbls/herumi.go:249-286 ThresholdAggregate — Lagrange interpolation at
the share indices; ref: tbls/herumi.go:288 Verify — one pairing per call),
these kernels take whole [num_validators, threshold] / [num_sigs] batches
and execute them as single XLA programs.

Kernel-shape discipline: public entry points pad the batch axis to the next
power of two and cache one compiled program per (kernel, padded-shape,
threshold) key, so steady-state slot processing never recompiles.

Identity encoding: affine (0, 0) lanes are group identities throughout
(safe on these curves since b != 0 means y = 0 never occurs).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from charon_tpu.ops import curve as C
from charon_tpu.ops import decompress as DEC
from charon_tpu.ops import fptower as T
from charon_tpu.ops import limb
from charon_tpu.ops import pairing as DP
from charon_tpu.ops import sswu as SSWU
from charon_tpu.ops.limb import ModCtx


def next_pow2(n: int) -> int:
    """Padded batch size: next power of two, minimum 4 — so every small
    call shares one compiled program (kernel-shape discipline)."""
    return max(4, 1 << max(0, (n - 1)).bit_length())


_next_pow2 = next_pow2  # internal alias (pre-bucketing name)


def bucket_lanes(n: int, multiple: int = 1) -> int:
    """THE shape-bucket ladder every batched entry point pads to:
    `multiple * pow2(ceil(n / multiple))`.

    `multiple` is the mesh shard count for sharded planes (the padded
    batch must split evenly over shards) and 1 for single-device
    engines, where this reduces to plain next_pow2 with its 4-lane
    floor. Sharded planes use a per-shard floor of 1 instead — the
    shard count is already their batch floor, so small slot workloads
    keep the cheap `multiple`-lane program. Using one ladder for
    BlsEngine AND the coalescer's sharded flushes keeps the jit cache
    bounded at O(log max_batch) compiled programs per kernel family —
    arbitrary flush sizes land on pre-declared bucket shapes instead of
    compiling per size (ISSUE 3: unify shape bucketing)."""
    if multiple <= 0:
        raise ValueError("multiple must be positive")
    if multiple == 1:
        return next_pow2(n)
    per_shard = -(-n // multiple)
    return multiple * (1 << max(0, (per_shard - 1)).bit_length())


# Every jitted kernel this module builds registers here so tests (and
# operators via bench tooling) can measure COMPILED PROGRAM counts —
# the regression signal for unbounded jit-cache growth when a caller
# bypasses the bucket ladder.
_JIT_KERNELS: list = []


def _jit_kernel(fn):
    jitted = jax.jit(fn)
    _JIT_KERNELS.append(jitted)
    return jitted


def jit_cache_size() -> int:
    """Total compiled-program count across this module's live jitted
    kernels. Bounded by (kernel families) x (bucket-ladder shapes) —
    asserted in tests/test_hostplane.py across random-size flushes."""
    return sum(k._cache_size() for k in _JIT_KERNELS)


# ---------------------------------------------------------------------------
# Named kernel-family registry (ISSUE 11)
# ---------------------------------------------------------------------------
#
# _JIT_KERNELS above counts compiled programs but is anonymous — it can
# tell you HOW MANY programs exist, not WHICH. The named registry below
# is the machine-readable kernel inventory: every device-graph family
# registers a build closure that returns a traceable (fn, canonical
# args) pair on bucket-ladder shapes, so the static analyzer
# (charon_tpu/analysis/jaxpr_check.py) can jax.make_jaxpr each family
# WITHOUT executing it, and the future per-platform auto-tuner
# (ROADMAP item 3) can enumerate candidates. Registration is cheap
# (closures only); canonical inputs are built lazily at trace time.


@dataclasses.dataclass
class TraceSpec:
    """One traceable instantiation of a kernel family: the callable,
    canonical example args on a bucket-ladder shape, and the limb
    geometry the analyzer checks dtype invariants against."""

    fn: Callable
    args: tuple
    ctx: "ModCtx"
    lanes: int  # padded batch lanes (must sit on the bucket ladder)
    multiple: int = 1  # ladder multiple (mesh shard count; 1 = engine)


@dataclasses.dataclass(frozen=True)
class KernelFamily:
    """A registered device-graph family. `build()` -> TraceSpec.

    `sentinel` families are cheap to trace (~seconds) and are re-traced
    on EVERY `ci.sh analysis` run; non-sentinel families (the pairing
    graphs trace in 25-45 s each on one core) are covered by the
    manifest source digest and re-traced only when kernel sources
    change (jaxpr_check --full / --update)."""

    name: str
    build: Callable[[], TraceSpec]
    sentinel: bool = False


_KERNEL_FAMILIES: dict[str, KernelFamily] = {}


def register_kernel_family(
    name: str, build: Callable[[], TraceSpec], sentinel: bool = False
) -> None:
    if name in _KERNEL_FAMILIES:
        raise ValueError(f"kernel family {name!r} already registered")
    _KERNEL_FAMILIES[name] = KernelFamily(name, build, sentinel)


def kernel_families() -> dict[str, KernelFamily]:
    """Snapshot of the registry (engine families at import time; mesh
    plane variants after parallel.mesh.register_analysis_families())."""
    return dict(_KERNEL_FAMILIES)


def _register_engine_families() -> None:
    """Register this module's kernel families on canonical shapes.

    Canonical lanes = 4 (the ladder floor) keeps trace time minimal —
    the primitive census is shape-stable per family, so one ladder
    point pins the graph. Both limb geometries register for the cheap
    families: the uint32 (TPU) geometry is where a stray 64-bit
    widening or float promotion would actually hurt, so the sentinels
    cover it every run."""
    from charon_tpu.ops import curve as _C

    def _pts(ctx, n):
        from charon_tpu.crypto.g1g2 import G1_GEN, G2_GEN

        return (
            _C.g1_pack(ctx, [G1_GEN] * n),
            _C.g2_pack(ctx, [G2_GEN] * n),
            _C.g2_pack(ctx, [G2_GEN] * n),
        )

    def _grid(tree, t):
        return jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * t, axis=1), tree
        )

    n = 4
    t = 3

    def _verify(ctx, fr_ctx):
        pk, msg, sig = _pts(ctx, n)
        return TraceSpec(_verify_kernel(ctx), (pk, msg, sig), ctx, n)

    def _verify_rlc(ctx, fr_ctx):
        pk, msg, sig = _pts(ctx, n)
        rand = jnp.asarray(limb.ctx_pack(fr_ctx, [1] * n))
        return TraceSpec(
            _verify_rlc_kernel(ctx, fr_ctx), (pk, msg, sig, rand), ctx, n
        )

    def _verify_grouped(ctx, fr_ctx):
        pk, msg, sig = _pts(ctx, n * n)
        gridify = lambda tree: jax.tree_util.tree_map(
            lambda a: a.reshape(n, n, *a.shape[1:]), tree
        )
        rand = jnp.asarray(
            np.asarray(limb.ctx_pack(fr_ctx, [1] * (n * n))).reshape(
                n, n, -1
            )
        )
        return TraceSpec(
            _verify_grouped_rlc_kernel(ctx, fr_ctx),
            (gridify(pk), _pts(ctx, n)[1], gridify(sig), rand),
            ctx,
            n,
        )

    def _thr_agg(ctx, fr_ctx):
        _, _, sig = _pts(ctx, n)
        idx = jnp.asarray(
            np.tile(np.arange(1, t + 1, dtype=np.int32), (n, 1))
        )
        return TraceSpec(
            _threshold_agg_kernel(ctx, fr_ctx, t),
            (_grid(sig, t), idx),
            ctx,
            n,
        )

    def _agg(ctx, fr_ctx):
        _, _, sig = _pts(ctx, n)
        return TraceSpec(_aggregate_kernel(ctx, t), (_grid(sig, t),), ctx, n)

    def _g1sum(ctx, fr_ctx):
        pk, _, _ = _pts(ctx, n)
        return TraceSpec(_g1_sum_kernel(ctx, t), (_grid(pk, t),), ctx, n)

    def _sub_g2(ctx, fr_ctx):
        _, _, sig = _pts(ctx, n)
        order = jnp.asarray(limb.ctx_pack(fr_ctx, [fr_ctx.modulus] * n))
        return TraceSpec(
            _subgroup_g2_kernel(ctx, fr_ctx), (sig, order), ctx, n
        )

    def _sub_g1(ctx, fr_ctx):
        pk, _, _ = _pts(ctx, n)
        order = jnp.asarray(limb.ctx_pack(fr_ctx, [fr_ctx.modulus] * n))
        return TraceSpec(
            _subgroup_g1_kernel(ctx, fr_ctx), (pk, order), ctx, n
        )

    def _dec_g2(ctx, fr_ctx):
        from charon_tpu.crypto.g1g2 import G2_GEN, g2_to_bytes

        parsed = [DEC.parse_g2_lane(g2_to_bytes(G2_GEN))] * n
        return TraceSpec(
            _decompress_g2_kernel(ctx, fr_ctx, True),
            DEC.pack_parsed_g2(ctx, parsed),
            ctx,
            n,
        )

    def _dec_g1(ctx, fr_ctx):
        from charon_tpu.crypto.g1g2 import G1_GEN, g1_to_bytes

        parsed = [DEC.parse_g1_lane(g1_to_bytes(G1_GEN))] * n
        return TraceSpec(
            _decompress_g1_kernel(ctx, fr_ctx, True),
            DEC.pack_parsed_g1(ctx, parsed),
            ctx,
            n,
        )

    def _h2c(ctx, fr_ctx):
        lanes = [SSWU.hash_to_field_lane(b"jaxpr-check", SSWU.DST_POP)] * n
        return TraceSpec(
            _hash_to_g2_kernel(ctx, fr_ctx),
            SSWU.pack_hashed(ctx, lanes),
            ctx,
            n,
        )

    def _g1_mul(ctx, fr_ctx):
        pk, _, _ = _pts(ctx, n)
        s = _C.fr_pack(fr_ctx, [1] * n)
        return TraceSpec(
            _g1_scalar_mul_kernel(ctx, fr_ctx), (pk, s), ctx, n
        )

    def _g2_mul(ctx, fr_ctx):
        _, _, sig = _pts(ctx, n)
        s = _C.fr_pack(fr_ctx, [1] * n)
        return TraceSpec(
            _g2_scalar_mul_kernel(ctx, fr_ctx), (sig, s), ctx, n
        )

    def _gen_mul(ctx, fr_ctx):
        s = _C.fr_pack(fr_ctx, [1] * n)
        return TraceSpec(
            _g1_gen_mul_kernel(ctx, fr_ctx, 255, 4), (s,), ctx, n
        )

    def _ceval(ctx, fr_ctx):
        pk, _, _ = _pts(ctx, n * t)
        grid = jax.tree_util.tree_map(
            lambda a: a.reshape(n, t, *a.shape[1:]), pk
        )
        xs = jnp.arange(1, n + 1, dtype=jnp.int32)
        return TraceSpec(
            _commitment_eval_kernel(ctx, fr_ctx, 1, t, 32), (grid, xs), ctx, n
        )

    def _g1msm(ctx, fr_ctx):
        pk, _, _ = _pts(ctx, n)
        s = jnp.asarray(limb.ctx_pack(fr_ctx, [1] * n))
        seg = jnp.zeros((n,), jnp.int32)
        return TraceSpec(
            _g1_msm_kernel(ctx, fr_ctx, 1, 255), (pk, s, seg), ctx, n
        )

    def _lag_at(ctx, fr_ctx):
        idx = jnp.asarray(
            np.tile(np.arange(1, t + 1, dtype=np.int32), (n, 1))
        )
        xs = jnp.arange(1, n + 1, dtype=jnp.int32)
        return TraceSpec(_lagrange_at_kernel(fr_ctx, t), (idx, xs), ctx, n)

    heavy = {
        "verify": _verify,
        "verify_rlc": _verify_rlc,
        "verify_grouped_rlc": _verify_grouped,
        "threshold_agg": _thr_agg,
        "hash_to_g2": _h2c,
        # ceremony families (ISSUE 20): fixed-base gather-adds, the
        # Straus/per-lane commitment evaluation, and the reshare
        # Pippenger MSM — curve-heavy graphs, digest-covered
        "g1_gen_mul": _gen_mul,
        "commitment_eval": _ceval,
        "g1_msm": _g1msm,
    }
    cheap = {
        "aggregate": _agg,
        "g1_sum": _g1sum,
        "subgroup_g2": _sub_g2,
        "subgroup_g1": _sub_g1,
        "decompress_g2": _dec_g2,
        "decompress_g1": _dec_g1,
        "g1_scalar_mul": _g1_mul,
        "g2_scalar_mul": _g2_mul,
        # pure-Fr Lagrange rows at arbitrary points (resharing): cheap
        # enough to sentinel-trace every analysis run
        "lagrange_at": _lag_at,
    }

    def _bind(builder):
        # default (CPU, 24-bit/uint64) geometry
        return lambda: builder(limb.default_fp_ctx(), limb.default_fr_ctx())

    def _bind32(builder):
        # TPU (12-bit/uint32) geometry — the widening check's real target
        return lambda: builder(limb.FP32, limb.FR32)

    for fname, builder in heavy.items():
        register_kernel_family(f"blsops/{fname}", _bind(builder))
    for fname, builder in cheap.items():
        register_kernel_family(
            f"blsops/{fname}", _bind(builder), sentinel=True
        )
    # uint32-geometry sentinels: cheap ladder kernels where an implicit
    # 64-bit promotion would silently wreck TPU throughput
    for fname in (
        "subgroup_g1",
        "g1_scalar_mul",
        "decompress_g1",
        "lagrange_at",
    ):
        register_kernel_family(
            f"blsops32/{fname}", _bind32(cheap[fname]), sentinel=True
        )


_register_engine_families()


# ---------------------------------------------------------------------------
# Device Lagrange coefficients at zero (Fr)
# ---------------------------------------------------------------------------


def _indices_to_fr(fr_ctx: ModCtx, idx):
    """int32 share indices (..., ) -> raw Fr limb arrays.

    Supports indices up to 2^(2*limb_bits) (far beyond any cluster size)."""
    idx = idx.astype(jnp.uint32)
    lo = (idx & np.uint32(fr_ctx.mask)).astype(fr_ctx.dtype)
    hi = (idx >> np.uint32(fr_ctx.limb_bits)).astype(fr_ctx.dtype)
    out = limb.zeros(fr_ctx, idx.shape)
    out = out.at[..., 0].set(lo)
    out = out.at[..., 1].set(hi)
    return out


def lagrange_coeffs_at_zero(fr_ctx: ModCtx, idx, t: int):
    """Batched Lagrange basis at x=0: idx is (..., t) int32 of distinct
    nonzero share indices; returns raw Fr limbs (..., t, n_limbs).

        coeff_j = prod_{m != j} x_m / (x_m - x_j)   (mod r)

    (spec: charon_tpu/crypto/shamir.py:45). t is static and small, so the
    j/m loops unroll; the inversions are one vectorized Fermat chain.
    """
    x_mont = limb.to_mont(fr_ctx, _indices_to_fr(fr_ctx, idx))  # (..., t, L)
    xs = [x_mont[..., j, :] for j in range(t)]
    nums, dens = [], []
    for j in range(t):
        num = None
        den = None
        for m in range(t):
            if m == j:
                continue
            num = xs[m] if num is None else limb.mont_mul(fr_ctx, num, xs[m])
            d = limb.sub_mod(fr_ctx, xs[m], xs[j])
            den = d if den is None else limb.mont_mul(fr_ctx, den, d)
        if num is None:  # t == 1
            num = limb.const(fr_ctx, 1, xs[j].shape[:-1])
            den = limb.const(fr_ctx, 1, xs[j].shape[:-1])
        nums.append(num)
        dens.append(den)
    num = jnp.stack(nums, axis=-2)  # (..., t, L)
    den = jnp.stack(dens, axis=-2)
    coeff = limb.mont_mul(fr_ctx, num, limb.inv_mod(fr_ctx, den))
    return limb.from_mont(fr_ctx, coeff)  # raw, for the bit schedule


def lagrange_coeffs_at(fr_ctx: ModCtx, idx, t: int, xs):
    """Batched Lagrange basis at ARBITRARY evaluation points — the
    resharing generalization of lagrange_coeffs_at_zero (ISSUE 20).

        coeff_j(x) = prod_{m != j} (x - x_m) / (x_j - x_m)   (mod r)

    idx is (..., t) int32 of distinct share indices; xs is (...,) int32
    evaluation points (one per batch lane). Returns raw Fr limbs
    (..., t, n_limbs). At x = 0 this reduces to the zero-point basis
    above (kept as separate code so the blessed duty-path graph is
    untouched)."""
    x_mont = limb.to_mont(fr_ctx, _indices_to_fr(fr_ctx, idx))  # (..., t, L)
    e_mont = limb.to_mont(fr_ctx, _indices_to_fr(fr_ctx, xs))  # (..., L)
    pts = [x_mont[..., j, :] for j in range(t)]
    nums, dens = [], []
    for j in range(t):
        num = None
        den = None
        for m in range(t):
            if m == j:
                continue
            nm = limb.sub_mod(fr_ctx, e_mont, pts[m])
            num = nm if num is None else limb.mont_mul(fr_ctx, num, nm)
            d = limb.sub_mod(fr_ctx, pts[j], pts[m])
            den = d if den is None else limb.mont_mul(fr_ctx, den, d)
        if num is None:  # t == 1
            num = limb.const(fr_ctx, 1, pts[j].shape[:-1])
            den = limb.const(fr_ctx, 1, pts[j].shape[:-1])
        nums.append(num)
        dens.append(den)
    num = jnp.stack(nums, axis=-2)  # (..., t, L)
    den = jnp.stack(dens, axis=-2)
    coeff = limb.mont_mul(fr_ctx, num, limb.inv_mod(fr_ctx, den))
    return limb.from_mont(fr_ctx, coeff)


def _mont_powers(fr_ctx: ModCtx, xs, t: int):
    """int32 evaluation points (...,) -> Montgomery-domain powers
    x^0..x^(t-1), shape (..., t, n_limbs). t is static and small, so the
    chain unrolls into t-1 mont_muls."""
    x = limb.to_mont(fr_ctx, _indices_to_fr(fr_ctx, xs))
    pows = [limb.const(fr_ctx, 1, x.shape[:-1])]
    for _ in range(1, t):
        pows.append(limb.mont_mul(fr_ctx, pows[-1], x))
    return jnp.stack(pows, axis=-2)


# ---------------------------------------------------------------------------
# Raw (already-packed) kernels — jit-compiled once per padded shape
# ---------------------------------------------------------------------------


def clear_kernel_caches() -> None:
    """Drop every cached jitted kernel so the next call RE-TRACES.

    The degradation ladders (bench.py, tbls/tpu_impl.py) flip trace-time
    routing flags (fptower.set_fp2_fusion, limb.set_pallas, limb.set_mxu,
    msm.set_msm); without this, the lru-cached jit wrappers — including
    _threshold_agg_kernel's Straus/per-lane routing — keep returning the
    already-compiled executable and the flag flip never takes effect."""
    import sys

    mod = sys.modules[__name__]
    for name in dir(mod):
        fn = getattr(mod, name)
        if callable(fn) and hasattr(fn, "cache_clear"):
            fn.cache_clear()
    _JIT_KERNELS.clear()  # dropped with their lru entries — don't leak


def threshold_recombine(ctx: ModCtx, fr_ctx: ModCtx, t: int, sig_affine, idx):
    """(V, t) affine G2 share sigs + (V, t) int32 share indices -> [V]
    affine group signatures. THE threshold-recombination routine — the
    single place that decides Straus joint windowed mul (one shared
    doubling chain per validator, ops/msm.py) vs the per-lane four-base
    multiplication over psi (curve.g2_scalar_mul_psi: 64 joint steps a
    lane, not 255); both _threshold_agg_kernel and the sharded mesh
    plane (parallel/mesh.py) call it.

    PRECONDITION: every share sig is a point of order r (or the (0, 0)
    identity). The four-base form rests on psi(P) == [x]P, which holds on
    G2 and nowhere else on the twist; this routine does not check again.
    Where each caller's check happens: `step_rlc_dec` / `step_dec`
    (parallel/mesh.py, the served path) decompress the partials in the
    program, and decompress_g2_graph's psi subgroup check turns a lane
    that fails into the identity and its row's `row_ok` off;
    BLSOps.threshold_aggregate_batch takes what tbls/tpu_impl._sig_points
    decoded (subgroup-checked: `verify_inputs`); core/autotune's probe
    multiplies the generator. `step_rlc` / `step` on POINTS take what the
    coalescer's host decode rung made (core/cryptoplane._decode_sig: on
    the curve, NOT subgroup-checked, there as before this form): a partial
    outside G2 gave a group signature outside G2 under the 255-step
    ladder and gives another such here, and the row's group check judges
    it either way."""
    f = C.g2_ops(ctx)
    coeffs = lagrange_coeffs_at_zero(fr_ctx, idx, t)  # (V, t, L)
    proj = C.affine_to_point(f, sig_affine)
    from charon_tpu.ops import msm as MSM

    if MSM.msm_active():
        total = MSM.windowed_joint_mul(f, fr_ctx, proj, coeffs)
    else:
        # The V * t multiplications run as FLAT lanes and take the (V, t)
        # grid back only for the fold over t: with t in an array's
        # second-minor place the TPU compiler tiles a power-of-two t by
        # (t, 128) and the scan relays every stacked product out — at
        # t = 4 the step program read 1.67 s where t = 3 / 5 read 0.87 /
        # 1.10 (PERF.md §6, PR 40). Flat lanes are what the verify
        # programs run on.
        v = idx.shape[0]
        flat = lambda a: a.reshape(v * t, *a.shape[2:])
        grid = lambda a: a.reshape(v, t, *a.shape[1:])
        scaled = C.g2_scalar_mul_psi(
            ctx, fr_ctx, jax.tree_util.tree_map(flat, sig_affine), flat(coeffs)
        )
        # the fold over t is a scan of t - 1 adds on V rows: one add site
        # in the module whatever t (each unrolled one is 13 MB of code)
        parts = jax.tree_util.tree_map(lambda a: jnp.moveaxis(grid(a), 1, 0), scaled)
        total, _ = jax.lax.scan(
            lambda acc, part: (C.point_add(f, acc, part), None),
            jax.tree_util.tree_map(lambda a: a[0], parts),
            jax.tree_util.tree_map(lambda a: a[1:], parts),
        )
    return C.point_to_affine(f, total)


@functools.lru_cache(maxsize=None)
def _threshold_agg_kernel(ctx: ModCtx, fr_ctx: ModCtx, t: int):
    return _jit_kernel(
        lambda sig_affine, idx: threshold_recombine(
            ctx, fr_ctx, t, sig_affine, idx
        )
    )


@functools.lru_cache(maxsize=None)
def _verify_kernel(ctx: ModCtx):
    return _jit_kernel(functools.partial(DP.batched_verify, ctx))


@functools.lru_cache(maxsize=None)
def _verify_rlc_kernel(ctx: ModCtx, fr_ctx: ModCtx):
    return _jit_kernel(functools.partial(DP.batched_verify_rlc, ctx, fr_ctx))


@functools.lru_cache(maxsize=None)
def _verify_grouped_rlc_kernel(ctx: ModCtx, fr_ctx: ModCtx):
    return _jit_kernel(
        functools.partial(DP.batched_verify_grouped_rlc, ctx, fr_ctx)
    )


@functools.lru_cache(maxsize=None)
def _aggregate_kernel(ctx: ModCtx, k: int):
    """Sum k G2 points per lane (signature aggregation)."""
    f = C.g2_ops(ctx)

    def kernel(sig_affine):
        proj = C.affine_to_point(f, sig_affine)
        return C.point_to_affine(f, C.point_sum(f, proj, axis=-1))

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _g1_sum_kernel(ctx: ModCtx, k: int):
    f = C.g1_ops(ctx)

    def kernel(pk_affine):
        proj = C.affine_to_point(f, pk_affine)
        return C.point_to_affine(f, C.point_sum(f, proj, axis=-1))

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _subgroup_g2_kernel(ctx: ModCtx, fr_ctx: ModCtx):
    f = C.g2_ops(ctx)

    def kernel(pts, order):
        proj = C.affine_to_point(f, pts)
        rp = C.point_scalar_mul(f, fr_ctx, proj, order)
        return C.point_is_identity(f, rp)

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _subgroup_g1_kernel(ctx: ModCtx, fr_ctx: ModCtx):
    f = C.g1_ops(ctx)

    def kernel(pts, order):
        proj = C.affine_to_point(f, pts)
        rp = C.point_scalar_mul(f, fr_ctx, proj, order)
        return C.point_is_identity(f, rp)

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _decompress_g2_kernel(ctx: ModCtx, fr_ctx: ModCtx, subgroup: bool):
    """Compressed-G2 field work + (optionally) the psi subgroup check in
    ONE program — the decode stage of a flush no longer pays a separate
    subgroup_check_g2_batch dispatch (ISSUE 5)."""
    return _jit_kernel(
        lambda x0, x1, sign, inf, ok: DEC.decompress_g2_graph(
            ctx, fr_ctx, (x0, x1), sign, inf, ok, subgroup=subgroup
        )
    )


@functools.lru_cache(maxsize=None)
def _decompress_g1_kernel(ctx: ModCtx, fr_ctx: ModCtx, subgroup: bool):
    return _jit_kernel(
        lambda x0, sign, inf, ok: DEC.decompress_g1_graph(
            ctx, fr_ctx, x0, sign, inf, ok, subgroup=subgroup
        )
    )


@functools.lru_cache(maxsize=None)
def _hash_to_g2_kernel(ctx: ModCtx, fr_ctx: ModCtx):
    """Device hash-to-curve tail (ISSUE 6): SSWU + 3-isogeny + psi
    cofactor clearing in ONE program — the host ships only the cheap
    SHA-256 hash_to_field outputs (ops/sswu.py)."""
    return _jit_kernel(
        lambda u00, u01, u10, u11, s0, s1: SSWU.hash_to_g2_graph(
            ctx, fr_ctx, (u00, u01), (u10, u11), s0, s1
        )
    )


@functools.lru_cache(maxsize=None)
def _g1_scalar_mul_kernel(ctx: ModCtx, fr_ctx: ModCtx):
    f = C.g1_ops(ctx)

    def kernel(base_affine, scalars):
        proj = C.affine_to_point(f, base_affine)
        return C.point_to_affine(f, C.point_scalar_mul(f, fr_ctx, proj, scalars))

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _g2_scalar_mul_kernel(ctx: ModCtx, fr_ctx: ModCtx):
    f = C.g2_ops(ctx)

    def kernel(base_affine, scalars):
        proj = C.affine_to_point(f, base_affine)
        return C.point_to_affine(f, C.point_scalar_mul(f, fr_ctx, proj, scalars))

    return _jit_kernel(kernel)


# ---------------------------------------------------------------------------
# Ceremony kernels: DKG verification + key resharing (ISSUE 20)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gen_table_g1(ctx: ModCtx, nbits: int, window: int):
    """Fixed-base window table for the G1 generator: packed affine
    multiples T[win][d] = d * 2^(window*win) * G, computed ONCE on the
    host (public constants). With the table baked into the graph the
    kernel needs zero doublings — one gathered add per window."""
    from charon_tpu.crypto.g1g2 import G1_GEN, g1_add

    n_win = -(-nbits // window)
    flat = []
    base = G1_GEN
    for _ in range(n_win):
        entry = None
        for _d in range(1 << window):
            flat.append(entry)
            entry = g1_add(entry, base)
        for _ in range(window):
            base = g1_add(base, base)
    packed = C.g1_pack(ctx, flat)
    return jax.tree_util.tree_map(
        lambda a: a.reshape(n_win, 1 << window, *a.shape[1:]), packed
    )


@functools.lru_cache(maxsize=None)
def _g1_gen_mul_kernel(ctx: ModCtx, fr_ctx: ModCtx, nbits: int, window: int):
    """Batched fixed-base scalar mul [k_i] G — the DKG share/PoK check
    LHS. Replaces the generic 255-double ladder with table gathers:
    ~nbits/window complete adds per lane, no doublings."""
    f = C.g1_ops(ctx)
    from charon_tpu.ops import msm as MSM

    table = _gen_table_g1(ctx, nbits, window)
    n_win = -(-nbits // window)

    def kernel(scalars):
        digits = MSM._digits(fr_ctx, scalars, nbits, window)  # (N, n_win)
        win = jnp.arange(n_win, dtype=jnp.int32)[None, :]
        sel = jax.tree_util.tree_map(lambda a: a[win, digits], table)
        proj = C.affine_to_point(f, sel)  # batch (N, n_win)
        # reduce the window axis with a lax.scan — ONE add body in the
        # compiled graph instead of n_win-1 unrolled point adds
        from jax import lax

        xs = jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 1, 0), proj)
        template = jax.tree_util.tree_leaves(proj)[0][:, 0]
        init = jax.tree_util.tree_map(
            lambda a: limb.match_vary(a, template),
            C.point_identity(f, (digits.shape[0],)),
        )
        acc, _ = lax.scan(
            lambda acc, p: (C.point_add(f, acc, p), None), init, xs
        )
        return C.point_to_affine(f, acc)

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _commitment_eval_kernel(
    ctx: ModCtx, fr_ctx: ModCtx, vecs: int, t: int, nbits: int
):
    """Per lane: sum over `vecs` commitment vectors of sum_k C_k x^k —
    the Feldman/FROST commitment-polynomial evaluation that dominates
    ceremony verification. The x^k powers are built in-graph from the
    public int32 evaluation point; routing between Straus joint
    windowed mul (one shared doubling chain over all vecs*t points per
    lane) and per-lane double-and-add is owned by
    core/autotune.KernelConfig via msm.set_ceremony_straus."""
    f = C.g1_ops(ctx)
    from charon_tpu.ops import msm as MSM

    def kernel(commit_affine, xs):
        # commit_affine: affine leaves (N, vecs*t, ...); xs: int32 (N,)
        pows = limb.from_mont(fr_ctx, _mont_powers(fr_ctx, xs, t))
        pows = jnp.tile(pows, (1, vecs, 1))  # (N, vecs*t, L)
        proj = C.affine_to_point(f, commit_affine)
        if MSM.ceremony_straus_active():
            total = MSM.windowed_joint_mul(
                f, fr_ctx, proj, pows, nbits=nbits, window=4
            )
        else:
            scaled = C.point_scalar_mul(f, fr_ctx, proj, pows, nbits=nbits)
            total = C.point_sum(f, scaled, axis=-1)
        return C.point_to_affine(f, total)

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _g1_msm_kernel(ctx: ModCtx, fr_ctx: ModCtx, n_segments: int, nbits: int):
    """Segmented G1 Pippenger MSM over full-width scalars — the reshare
    pubshare recombination sum_i lambda_i m^k D_ik. Window width is the
    autotuned ceremony axis (msm.ceremony_window)."""
    f = C.g1_ops(ctx)
    from charon_tpu.ops import msm as MSM

    def kernel(points_affine, scalars, segment_ids):
        proj = C.affine_to_point(f, points_affine)
        out = MSM.msm_segmented(
            f,
            fr_ctx,
            proj,
            scalars,
            segment_ids,
            n_segments,
            nbits=nbits,
            window=MSM.ceremony_window(),
        )
        return C.point_to_affine(f, out)

    return _jit_kernel(kernel)


@functools.lru_cache(maxsize=None)
def _lagrange_at_kernel(fr_ctx: ModCtx, t: int):
    """Batched Lagrange basis rows at arbitrary evaluation points (pure
    Fr — no curve ops)."""
    return _jit_kernel(
        lambda idx, xs: lagrange_coeffs_at(fr_ctx, idx, t, xs)
    )


# ---------------------------------------------------------------------------
# Host-facing batched operations (Python-int points in, results out)
# ---------------------------------------------------------------------------


class BlsEngine:
    """Batched BLS12-381 engine bound to a limb geometry.

    Host boundary: affine Python-int points in/out (the facade handles
    compressed-bytes conversion and caching). Every method pads its batch
    to a power of two so compiled kernels are reused across calls.
    """

    def __init__(self, ctx: ModCtx | None = None, fr_ctx: ModCtx | None = None):
        self.ctx = ctx or limb.default_fp_ctx()
        self.fr_ctx = fr_ctx or limb.default_fr_ctx()

    # -- verification -----------------------------------------------------

    def verify_batch(self, pks, msg_points, sigs) -> list[bool]:
        """Lane-wise: e(pk_i, H(m)_i) == e(G1, sig_i).

        pks: affine G1 (or None); msg_points: affine G2 hashed messages;
        sigs: affine G2 (or None). Identity-lane semantics are the caller's
        concern (the facade rejects infinite pubkeys up front).
        """
        n = len(pks)
        pad = _next_pow2(n)
        pk = C.g1_pack(self.ctx, list(pks) + [None] * (pad - n))
        msg = C.g2_pack(self.ctx, list(msg_points) + [None] * (pad - n))
        sig = C.g2_pack(self.ctx, list(sigs) + [None] * (pad - n))
        ok = _verify_kernel(self.ctx)(pk, msg, sig)
        return [bool(b) for b in np.asarray(ok)[:n]]

    def verify_batch_rlc(self, pks, msg_points, sigs, rng=None) -> bool:
        """Whole-batch verification by random linear combination (see
        ops/pairing.batched_verify_rlc): one shared final exponentiation,
        2^-64 soundness per call with fresh OS randomness. None lanes
        (identity points) contribute neutrally — the caller tracks their
        validity separately. Returns a single bool; on False the caller
        re-runs verify_batch for per-lane attribution."""
        import random as _random

        rng = rng or _random.SystemRandom()
        n = len(pks)
        pad = _next_pow2(n)
        pk = C.g1_pack(self.ctx, list(pks) + [None] * (pad - n))
        msg = C.g2_pack(self.ctx, list(msg_points) + [None] * (pad - n))
        sig = C.g2_pack(self.ctx, list(sigs) + [None] * (pad - n))
        rand = jnp.asarray(
            limb.ctx_pack(
                self.fr_ctx,
                [rng.randrange(1, 1 << 64) for _ in range(n)]
                + [0] * (pad - n),
            )
        )
        ok = _verify_rlc_kernel(self.ctx, self.fr_ctx)(pk, msg, sig, rand)
        return bool(ok)

    def verify_batch_grouped_rlc(self, groups, rng=None) -> bool:
        """Grouped whole-batch verification
        (ops/pairing.batched_verify_grouped_rlc): `groups` is a list of
        (msg_point, [(pk_point, sig_point), ...]) — one entry per
        DISTINCT message. The Miller stage runs one pair per group plus
        one aggregate pair; per-lane cost is two 64-bit scalar muls.
        Grid dims are padded to powers of two so compiled kernels are
        reused across calls (pad lanes: identity points + zero
        exponents, which contribute neutrally). Returns a single bool."""
        import random as _random

        rng = rng or _random.SystemRandom()
        m = _next_pow2(len(groups))
        k = _next_pow2(max(len(lanes) for _, lanes in groups))
        pk_flat: list = []
        sig_flat: list = []
        rand_ints: list = []
        msg_list: list = []
        for msg_pt, lanes in groups:
            msg_list.append(msg_pt)
            for pk_pt, sig_pt in lanes:
                pk_flat.append(pk_pt)
                sig_flat.append(sig_pt)
                rand_ints.append(rng.randrange(1, 1 << 64))
            pad = k - len(lanes)
            pk_flat.extend([None] * pad)
            sig_flat.extend([None] * pad)
            rand_ints.extend([0] * pad)
        for _ in range(m - len(groups)):  # identity pad groups
            msg_list.append(None)
            pk_flat.extend([None] * k)
            sig_flat.extend([None] * k)
            rand_ints.extend([0] * k)

        def grid(packed):
            return jax.tree_util.tree_map(
                lambda a: a.reshape(m, k, *a.shape[1:]), packed
            )

        pk = grid(C.g1_pack(self.ctx, pk_flat))
        sig = grid(C.g2_pack(self.ctx, sig_flat))
        msg = C.g2_pack(self.ctx, msg_list)
        rand = jnp.asarray(
            np.asarray(limb.ctx_pack(self.fr_ctx, rand_ints)).reshape(
                m, k, -1
            )
        )
        ok = _verify_grouped_rlc_kernel(self.ctx, self.fr_ctx)(
            pk, msg, sig, rand
        )
        return bool(ok)

    # -- threshold recombination -----------------------------------------

    def threshold_aggregate_batch(self, partials: list[dict]) -> list:
        """Each entry maps share index -> affine G2 partial signature; all
        entries must share the same threshold t = len(dict). Returns the
        recombined affine G2 group signature per entry
        (spec: crypto/shamir.py:68; ref: tbls/herumi.go:249)."""
        if not partials:
            return []
        t = len(partials[0])
        if any(len(p) != t for p in partials):
            raise ValueError("all entries must have the same threshold")
        v = len(partials)
        pad = _next_pow2(v)
        idx = np.ones((pad, t), np.int32)
        idx[:, :] = np.arange(1, t + 1, dtype=np.int32)  # benign pad rows
        flat_sigs = []
        for row, p in enumerate(partials):
            items = sorted(p.items())
            idx[row] = [i for i, _ in items]
            flat_sigs.extend(s for _, s in items)
        flat_sigs.extend([None] * ((pad - v) * t))
        sig = C.g2_pack(self.ctx, flat_sigs)
        sig = jax.tree_util.tree_map(
            lambda a: a.reshape(pad, t, *a.shape[1:]), sig
        )
        out = _threshold_agg_kernel(self.ctx, self.fr_ctx, t)(
            sig, jnp.asarray(idx)
        )
        return C.g2_unpack(self.ctx, out)[:v]

    # -- plain aggregation (point addition) ------------------------------

    def aggregate_sigs_batch(self, groups: list[list]) -> list:
        """Sum each group of affine G2 signatures (ref: tbls/herumi.go:225
        Aggregate). Groups are padded to a common length with identities."""
        if not groups:
            return []
        k = max(len(g) for g in groups)
        v = len(groups)
        pad = _next_pow2(v)
        flat = []
        for g in groups:
            flat.extend(g)
            flat.extend([None] * (k - len(g)))
        flat.extend([None] * ((pad - v) * k))
        sig = C.g2_pack(self.ctx, flat)
        sig = jax.tree_util.tree_map(
            lambda a: a.reshape(pad, k, *a.shape[1:]), sig
        )
        out = _aggregate_kernel(self.ctx, k)(sig)
        return C.g2_unpack(self.ctx, out)[:v]

    def aggregate_pks_batch(self, groups: list[list]) -> list:
        """Sum each group of affine G1 pubkeys (FastAggregateVerify input)."""
        if not groups:
            return []
        k = max(len(g) for g in groups)
        v = len(groups)
        pad = _next_pow2(v)
        flat = []
        for g in groups:
            flat.extend(g)
            flat.extend([None] * (k - len(g)))
        flat.extend([None] * ((pad - v) * k))
        pk = C.g1_pack(self.ctx, flat)
        pk = jax.tree_util.tree_map(
            lambda a: a.reshape(pad, k, *a.shape[1:]), pk
        )
        out = _g1_sum_kernel(self.ctx, k)(pk)
        return C.g1_unpack(self.ctx, out)[:v]

    # -- subgroup membership ---------------------------------------------

    def subgroup_check_g2_batch(self, points) -> list[bool]:
        """[r]P == identity for decompressed (on-curve) G2 points — the
        prime-order subgroup check eth2 mandates before pairing. None lanes
        (identities) pass. Batched 255-bit ladder, one device call."""
        n = len(points)
        if n == 0:
            return []
        pad = _next_pow2(n)
        pts = C.g2_pack(self.ctx, list(points) + [None] * (pad - n))
        # Raw (unreduced!) group order as the ladder schedule.
        order = jnp.asarray(
            limb.ctx_pack(self.fr_ctx, [self.fr_ctx.modulus] * pad)
        )
        mask = _subgroup_g2_kernel(self.ctx, self.fr_ctx)(pts, order)
        return [bool(b) for b in np.asarray(mask)[:n]]

    def subgroup_check_g1_batch(self, points) -> list[bool]:
        n = len(points)
        if n == 0:
            return []
        pad = _next_pow2(n)
        pts = C.g1_pack(self.ctx, list(points) + [None] * (pad - n))
        order = jnp.asarray(
            limb.ctx_pack(self.fr_ctx, [self.fr_ctx.modulus] * pad)
        )
        mask = _subgroup_g1_kernel(self.ctx, self.fr_ctx)(pts, order)
        return [bool(b) for b in np.asarray(mask)[:n]]

    # -- batched point decompression -------------------------------------

    def decompress_g2_batch(self, encoded, subgroup_check: bool = True):
        """Compressed 96-byte G2 lanes -> ([affine point | None],
        [valid]) with the field work (sqrt, sign, on-curve, psi subgroup
        check) batched on device. Accepts raw bytes or pre-parsed
        decompress.ParsedPoint lanes. Per-lane semantics, never raises:
        valid=True with point=None is a well-formed infinity; valid=False
        covers malformed flags, x >= p, non-residue x and (when
        `subgroup_check`) non-subgroup points."""
        parsed = [
            p if isinstance(p, DEC.ParsedPoint) else DEC.parse_g2_lane(p)
            for p in encoded
        ]
        n = len(parsed)
        if n == 0:
            return [], []
        pad = bucket_lanes(n)
        parsed = parsed + [parsed[0]] * (pad - n)
        arrays = DEC.pack_parsed_g2(self.ctx, parsed)
        aff, valid = _decompress_g2_kernel(
            self.ctx, self.fr_ctx, subgroup_check
        )(*arrays)
        pts = C.g2_unpack(self.ctx, aff)[:n]
        return pts, [bool(b) for b in np.asarray(valid)[:n]]

    def decompress_g1_batch(self, encoded, subgroup_check: bool = True):
        """Compressed 48-byte G1 lanes -> ([affine point | None],
        [valid]); see decompress_g2_batch for the mask contract."""
        parsed = [
            p if isinstance(p, DEC.ParsedPoint) else DEC.parse_g1_lane(p)
            for p in encoded
        ]
        n = len(parsed)
        if n == 0:
            return [], []
        pad = bucket_lanes(n)
        parsed = parsed + [parsed[0]] * (pad - n)
        arrays = DEC.pack_parsed_g1(self.ctx, parsed)
        aff, valid = _decompress_g1_kernel(
            self.ctx, self.fr_ctx, subgroup_check
        )(*arrays)
        pts = C.g1_unpack(self.ctx, aff)[:n]
        return pts, [bool(b) for b in np.asarray(valid)[:n]]

    # -- batched hash-to-curve -------------------------------------------

    def hash_to_g2_batch(self, msgs, dst: bytes = SSWU.DST_POP):
        """Messages (raw bytes, or pre-hashed sswu.HashedMsg lanes) ->
        ([affine G2 point], [valid]) with the field work (SSWU +
        isogeny + psi cofactor clearing) batched on device; the host
        pays only expand_message_xmd/hash_to_field (SHA-256). The bulk
        cache warm-up path (ISSUE 6): a restart replays its message
        set through here instead of per-point python hash_to_curve.
        valid is always True for real lanes — carried per-lane so a
        degraded batch masks instead of raising."""
        lanes = [
            m if isinstance(m, SSWU.HashedMsg) else SSWU.hash_to_field_lane(m, dst)
            for m in msgs
        ]
        n = len(lanes)
        if n == 0:
            return [], []
        pad = bucket_lanes(n)
        lanes = lanes + [lanes[0]] * (pad - n)
        arrays = SSWU.pack_hashed(self.ctx, lanes)
        aff, valid = _hash_to_g2_kernel(self.ctx, self.fr_ctx)(*arrays)
        pts = C.g2_unpack(self.ctx, aff)[:n]
        return pts, [bool(b) for b in np.asarray(valid)[:n]]

    # -- scalar multiplication (DKG / key derivation) --------------------

    def g1_scalar_mul_batch(self, bases, scalars: list[int]) -> list:
        """[k_i] P_i over G1 — the DKG verification workhorse
        (ref: dkg/frost.go public-share checks)."""
        n = len(bases)
        pad = _next_pow2(n)
        base = C.g1_pack(self.ctx, list(bases) + [None] * (pad - n))
        s = C.fr_pack(self.fr_ctx, list(scalars) + [0] * (pad - n))
        out = _g1_scalar_mul_kernel(self.ctx, self.fr_ctx)(base, s)
        return C.g1_unpack(self.ctx, out)[:n]

    def g2_scalar_mul_batch(self, bases, scalars: list[int]) -> list:
        n = len(bases)
        pad = _next_pow2(n)
        base = C.g2_pack(self.ctx, list(bases) + [None] * (pad - n))
        s = C.fr_pack(self.fr_ctx, list(scalars) + [0] * (pad - n))
        out = _g2_scalar_mul_kernel(self.ctx, self.fr_ctx)(base, s)
        return C.g2_unpack(self.ctx, out)[:n]

    # -- ceremony kernels (DKG verification + resharing, ISSUE 20) -------

    @staticmethod
    def _eval_nbits(t: int, xs) -> int:
        """Tight-but-bucketed bit schedule for x^k powers: the raw values
        are bounded by max(x)^(t-1), so small evaluation points (share
        indices) need nowhere near 255 bits. Bucketing to a short ladder
        keeps the compiled-variant count bounded."""
        mx = max((int(x) for x in xs), default=1)
        need = max(1, t - 1) * max(1, mx.bit_length()) + 1
        for cand in (32, 64, 128):
            if need <= cand:
                return cand
        return 255

    def g1_gen_mul_batch(self, scalars: list[int]) -> list:
        """[k_i] G over G1 via the fixed-base window table — the DKG
        share/PoK verification LHS (public derived points; the scalar
        inputs are shares the CALLER owns — they ride the device only as
        packed limbs and come back as public curve points)."""
        n = len(scalars)
        if n == 0:
            return []
        pad = bucket_lanes(n)
        s = C.fr_pack(self.fr_ctx, list(scalars) + [0] * (pad - n))
        out = _g1_gen_mul_kernel(self.ctx, self.fr_ctx, 255, 4)(s)
        return C.g1_unpack(self.ctx, out)[:n]

    def commitment_eval_batch(self, commit_rows, xs: list[int], t: int) -> list:
        """Evaluate commitment polynomials at public points, one lane per
        row: row i is a flat tuple of vecs*t affine G1 commitments (vecs
        concatenated degree-(t-1) vectors) and the result is
        sum_vec sum_k C_k * xs[i]^k. THE ceremony-verification bulk."""
        n = len(commit_rows)
        if n == 0:
            return []
        width = len(commit_rows[0])
        if width % t or any(len(r) != width for r in commit_rows):
            raise ValueError("commitment rows must share one vecs*t width")
        vecs = width // t
        pad = bucket_lanes(n)
        flat: list = []
        for row in commit_rows:
            flat.extend(row)
        flat.extend([None] * ((pad - n) * width))
        commits = C.g1_pack(self.ctx, flat)
        commits = jax.tree_util.tree_map(
            lambda a: a.reshape(pad, width, *a.shape[1:]), commits
        )
        xs_arr = jnp.asarray(
            np.asarray(list(xs) + [0] * (pad - n), np.int32)
        )
        nbits = self._eval_nbits(t, xs)
        out = _commitment_eval_kernel(self.ctx, self.fr_ctx, vecs, t, nbits)(
            commits, xs_arr
        )
        return C.g1_unpack(self.ctx, out)[:n]

    def g1_msm_batch(
        self, points, scalars: list[int], segment_ids: list[int], n_segments: int
    ) -> list:
        """Segmented multi-scalar multiplication over G1 with full-width
        scalars: out[s] = sum_{i: seg[i]==s} scalars[i] * points[i] — the
        reshare pubshare recombination shape (Pippenger)."""
        if n_segments <= 0:
            return []
        n = len(points)
        seg_pad = _next_pow2(n_segments)
        pad = bucket_lanes(max(n, 1))
        pts = C.g1_pack(self.ctx, list(points) + [None] * (pad - n))
        s = C.fr_pack(self.fr_ctx, list(scalars) + [0] * (pad - n))
        seg = jnp.asarray(
            np.asarray(list(segment_ids) + [0] * (pad - n), np.int32)
        )
        out = _g1_msm_kernel(self.ctx, self.fr_ctx, seg_pad, 255)(pts, s, seg)
        return C.g1_unpack(self.ctx, out)[:n_segments]

    def lagrange_coeffs_batch(
        self, idx_rows, xs: list[int]
    ) -> list[list[int]]:
        """Lagrange basis rows at arbitrary evaluation points: row i is a
        list of distinct share indices, xs[i] the evaluation point;
        returns the matching coefficient rows as Python ints (public
        values — functions of public indices only)."""
        n = len(idx_rows)
        if n == 0:
            return []
        t = len(idx_rows[0])
        if any(len(r) != t for r in idx_rows):
            raise ValueError("index rows must share one width")
        pad = bucket_lanes(n)
        benign = list(range(1, t + 1))
        idx = np.asarray(
            [list(r) for r in idx_rows] + [benign] * (pad - n), np.int32
        )
        xs_arr = jnp.asarray(
            np.asarray(list(xs) + [0] * (pad - n), np.int32)
        )
        out = _lagrange_at_kernel(self.fr_ctx, t)(jnp.asarray(idx), xs_arr)
        flat = limb.ctx_unpack(self.fr_ctx, np.asarray(out).reshape(pad * t, -1))
        return [flat[i * t : (i + 1) * t] for i in range(n)]


@functools.lru_cache(maxsize=None)
def default_engine() -> BlsEngine:
    return BlsEngine()
