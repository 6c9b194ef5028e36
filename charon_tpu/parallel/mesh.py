"""Sharded slot-level crypto step over a device mesh.

Design (SURVEY.md §2.4, §7): the two parallelism axes of the reference —
validator-set batching (axis №1) and share-index t-of-n recombination
(axis №2) — map to array dimensions [V, t]. V is sharded over the mesh's
'shards' axis with shard_map; t stays local (the Lagrange reduction is a
t-term point fold). The only cross-device communication is a psum of the
per-shard validity counts — kilobyte-scale, riding ICI.

This is the "training step" analogue of the framework: one call per slot
processes every validator's partial signatures — verify each against its
pubshare, recombine to group signatures, verify the group signature — as a
single compiled SPMD program.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from charon_tpu.ops import blsops
from charon_tpu.ops import curve as C
from charon_tpu.ops import decompress as DEC
from charon_tpu.ops import fptower as T
from charon_tpu.ops import limb
from charon_tpu.ops import pairing as DP
from charon_tpu.ops import sswu as SSWU
from charon_tpu.ops.limb import ModCtx


def _dedupe_buckets(lanes, bucket_fn):
    """Keep one representative lane count per padded bucket shape."""
    seen, out = set(), []
    for n in lanes:
        b = bucket_fn(n)
        if b not in seen:
            seen.add(b)
            out.append(n)
    return out


def make_mesh(devices=None, axis: str = "shards") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def make_mesh_2d(
    n_hosts: int, devices=None, axes: tuple[str, str] = ("dcn", "ici")
) -> Mesh:
    """Multi-host mesh layout: leading axis across hosts (DCN), trailing
    axis across each host's chips (ICI).

    The slot plane's only collective is a scalar psum, which XLA lowers
    to an intra-host reduce over the minor (ICI) axis first and a single
    tiny cross-host reduce after — the validator batch axis is sharded
    over BOTH axes (flattened), so all bulk data stays device-local and
    nothing bulk ever crosses DCN (scaling-book recipe: shard so
    collectives ride ICI; DCN carries only scalars here).

    On real multi-host TPU the device list comes from
    `jax.distributed.initialize()` + `jax.devices()`; in tests the same
    layout is exercised by reshaping the 8-device virtual CPU mesh to
    (2 hosts x 4 chips)."""
    devices = devices if devices is not None else jax.devices()
    devices = np.asarray(devices)
    if devices.size % n_hosts:
        raise ValueError(
            f"{devices.size} devices do not split over {n_hosts} hosts"
        )
    return Mesh(devices.reshape(n_hosts, -1), axes)


class LaneSets(NamedTuple):
    """Which segment of the set-wise RLC product each lane of a packed
    verify batch rides, and which segments hold more than one set (a
    failure there is one the RLC tier cannot bill to a set). Host
    arrays: they travel with the pack to the device stage."""

    seg: np.ndarray  # int32 [N_padded], < SlotCryptoPlane.VERIFY_SETS
    mixed: np.ndarray  # bool [VERIFY_SETS]


class SlotCryptoPlane:
    """The per-slot batched crypto program, sharded over a mesh.

    Inputs per slot (leading axis V = #validators, sharded):
      pubshares  [V, t]  affine G1 — per-share public keys
      msg        [V]     affine G2 — per-validator signing roots (hashed)
      partials   [V, t]  affine G2 — per-share partial signatures
      group_pk   [V]     affine G1 — group public keys
      indices    [V, t]  int32     — share indices (1-based)

    Outputs:
      group_sig  [V]  affine G2 — recombined signatures (sharded)
      sig_ok     [V]  bool      — per-partial verify AND group verify
      total_ok   []   int32     — cluster-wide count of fully-valid lanes
                                  (psum over shards)

    That is `step` / `step_dec`, the attribution programs. The programs a
    flush dispatches first (`step_rlc` / `step_rlc_dec`) recombine the
    same way and check the group signature ALONE, one pairing lane a
    row: every partial was verified against its pubshare when it entered
    the node (`_step_rlc_body`).
    """

    # segments of the parsed RLC verify's product: part of the traced
    # shape, not an option. 8 holds one partial-signature set per sender
    # plus the validator client's for every cluster of n <= 7
    VERIFY_SETS = 8

    def __init__(self, mesh: Mesh, t: int, ctx: ModCtx | None = None, fr_ctx: ModCtx | None = None):
        self.mesh = mesh
        self.t = t
        self.ctx = ctx or limb.default_fp_ctx()
        self.fr_ctx = fr_ctx or limb.default_fr_ctx()
        # all mesh axes shard the validator batch dim together: on a
        # 2D (dcn, ici) mesh the flattened sharding keeps bulk data
        # device-local and the scalar psum is the only cross-axis op
        self.axis = tuple(mesh.axis_names)
        self._step = self._build()
        self._step_rlc = self._build_rlc()
        self._verify = self._build_verify()
        self._verify_rlc = self._build_verify_rlc()
        # decode-fused variants (ISSUE 5): signatures arrive as parsed
        # compressed lanes and the program decompresses them on device
        # before verifying — the coalescer's `decode_mode device` path.
        # Construction is free (jit compiles lazily on first call), so
        # planes that never see parsed flushes never compile these.
        self._verify_dec = self._build_verify_dec()
        self._verify_rlc_dec = self._build_verify_rlc_dec()
        self._step_dec = self._build_dec()
        self._step_rlc_dec = self._build_rlc_dec()
        # bulk warm-up programs (ISSUE 6): sharded hash-to-curve and G1
        # decompression for the cold-path cache warm — one compiled
        # program feeds thousands of point-cache entries per dispatch.
        self._h2c = self._build_h2c()
        self._g1dec = self._build_g1dec()
        # per-program timing hook (ISSUE 19): callable(family, seconds,
        # lanes), family names matching kernel_families ("mesh/verify_rlc"
        # ...). Fired from the host dispatch methods around each compiled
        # program INCLUDING its result sync, so the per-family times sum
        # to (approximately) the flush device_span — app/planeprof feeds
        # tpu_plane_kernel_seconds from it. None (the default) costs one
        # attribute check per dispatch.
        self.on_program = None

    def _timed(self, family: str, lanes: int, fn):
        """Run one compiled-program dispatch (with its sync) under the
        timing hook. Hook faults never fail the dispatch."""
        hook = self.on_program
        if hook is None:
            return fn()
        t0 = time.monotonic()
        try:
            return fn()
        finally:
            try:
                hook(f"mesh/{family}", time.monotonic() - t0, lanes)
            except Exception:  # noqa: BLE001 — observability stays off the duty path
                pass

    def _step_body(self, pubshares, msg, partials, group_pk, indices, live):
        """Per-shard recombine + per-lane attribution verify. Shared by
        the point-input program and the decode-fused one (which ANDs its
        decompression mask into `live` before calling)."""
        ctx, fr_ctx, t, axis = self.ctx, self.fr_ctx, self.t, self.axis
        # Threshold recombination first [Vl] — it has no data dependency
        # on the verifies, and doing it first lets BOTH verify tiers run
        # as ONE batched pairing program over Vl*(t+1) lanes (a single
        # Miller-loop/final-exp subgraph in the compiled module instead
        # of two, which halves the dominant XLA compile cost and keeps
        # the device busy with one large batch instead of two smaller
        # ones).
        group_sig = blsops.threshold_recombine(ctx, fr_ctx, t, partials, indices)

        # Verify lanes: [Vl, t] per-share partials ++ [Vl, 1] group sig,
        # flattened to one [Vl*(t+1)] batch.
        cat = lambda a, b: jnp.concatenate(
            (a, b[:, None, ...]), axis=1
        ).reshape(-1, *a.shape[2:])
        pk_all = jax.tree_util.tree_map(cat, pubshares, group_pk)
        sig_all = jax.tree_util.tree_map(cat, partials, group_sig)
        msg_rep = jax.tree_util.tree_map(
            lambda a: jnp.repeat(a, t + 1, axis=0), msg
        )
        ok_all = DP.batched_verify(ctx, pk_all, msg_rep, sig_all)
        ok = jnp.all(ok_all.reshape(-1, t + 1), axis=-1)
        # `live` masks padding lanes (V rounded up to the mesh size)
        # out of the cluster-wide count
        ok = jnp.logical_and(ok, live)
        total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axis)
        return group_sig, ok, total

    def _build(self):
        axis = self.axis

        sharded = jax.shard_map(
            self._step_body,
            mesh=self.mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P()),
        )
        return jax.jit(sharded)

    def _build_dec(self):
        """Attribution recombine on PARSED partials: decompress the
        [Vl, t] signature grid in-program, then the shared step body.
        Rows with any undecodable partial recombine as identities and
        fail via the decode mask folded into `live`."""
        ctx, fr_ctx, axis = self.ctx, self.fr_ctx, self.axis

        def local_step(ps, msg, px0, px1, psign, gpk, idx, live):
            partials, dec_ok = DEC.decompress_g2_graph(
                ctx, fr_ctx, (px0, px1), psign
            )
            row_ok = jnp.all(dec_ok, axis=1)
            return self._step_body(
                ps, msg, partials, gpk, idx, jnp.logical_and(live, row_ok)
            )

        sharded = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                P(axis), P(axis),
            ),
            out_specs=(P(axis), P(axis), P()),
        )
        return jax.jit(sharded)

    def _build_rlc(self):
        """The throughput path: identical recombination, then ONE check a
        row — the recombined group signature under the group key — by
        random linear combination (ops/pairing.batched_verify_rlc): each
        shard product-trees its rows' pairing values and runs ONE local
        final exponentiation (all shards in parallel). Returns
        (group_sig, all_ok) where all_ok is the cluster-wide AND (psum of
        per-shard failures == 0). Which partial of a failing row is bad
        is the slower `step`'s to say (the reference verifies the
        aggregate alone here too — core/sigagg/sigagg.go:84-122)."""
        axis = self.axis

        sharded = jax.shard_map(
            self._step_rlc_body,
            mesh=self.mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)
            ),
            out_specs=(P(axis), P()),
        )
        return jax.jit(sharded)

    def _step_rlc_body(self, pubshares, msg, partials, group_pk, indices, live, rand):
        """Recombine each row's t partials and check the AGGREGATE:

            prod_v e(r_v * group_pk_v, H(m_v)) * e(r_v * (-G1), group_sig_v) == 1

        one pairing lane a row under an independent 64-bit exponent a
        row. The partials are not judged again here: each was verified
        against its pubshare when it entered the node (a peer's set in
        ParSigEx, the VC's in ValidatorAPI — the verify programs), and a
        set that failed never reached ParSigDB; upstream does the same
        (core/parsigex/parsigex.go verifies on receipt,
        core/sigagg/sigagg.go:84-122 the aggregate alone), and so does
        the host rung (core/sigagg._aggregate_via_tbls). A bad partial
        that did reach a row enters the group signature under a non-zero
        Lagrange coefficient, so the row fails and `step` attributes.
        The one input this accepts that a per-partial check refuses —
        partial errors crafted to cancel in the Lagrange sum — yields a
        VALID group signature, the object the duty broadcasts; upstream
        and the host rung accept it too. `pubshares` stays an argument
        (the attribution programs, which share the packs, read it)."""
        del pubshares
        ctx, fr_ctx, t, axis = self.ctx, self.fr_ctx, self.t, self.axis
        group_sig = blsops.threshold_recombine(ctx, fr_ctx, t, partials, indices)

        # Padding and undecodable rows carry live=False: zero their
        # exponent so their (possibly garbage) pairing value contributes
        # ^0 = 1.
        rand_live = jnp.where(live[:, None], rand, 0)

        from charon_tpu.ops import msm as MSM

        if MSM.msm_active():
            # Grouped form of the same equation, one lane a bucket: the
            # rows' r_v * group_sig_v collapse into ONE aggregate pair
            # e(-G1, sum_v r_v * group_sig_v), so the Miller stage runs
            # Vl + 1 pairs (pairing.grouped_rlc_check, the construction
            # of pairing.batched_verify_grouped_rlc with a group a row).
            g1f, g2f = C.g1_ops(ctx), C.g2_ops(ctx)

            def scaled(f, affine):  # [Vl] r_v * P_v, as a [Vl, 1] grid
                return MSM.windowed_joint_mul(
                    f,
                    fr_ctx,
                    jax.tree_util.tree_map(
                        lambda a: a[:, None, ...], C.affine_to_point(f, affine)
                    ),
                    rand_live[:, None],
                    nbits=64,
                )

            buckets = scaled(g1f, group_pk)
            sig_v = scaled(g2f, group_sig)
            s_total = DP.point_sum_tree(g2f, sig_v, live.shape[0])
            ok = DP.grouped_rlc_check(ctx, buckets, msg, s_total)
        else:
            ok = DP.batched_verify_rlc(
                ctx, fr_ctx, group_pk, msg, group_sig, rand_live
            )
        bad = jax.lax.psum(jnp.logical_not(ok).astype(jnp.int32), axis)
        return group_sig, bad == 0

    def _build_rlc_dec(self):
        """RLC recombine on PARSED partials: in-program decompression,
        then the shared body (one pairing lane a row: the group
        signature under the group key). Rows with undecodable partials
        are excluded from the shared product (exponent 0) and reported
        via the third output so the host can answer per row on the
        all-valid fast path."""
        ctx, fr_ctx, axis = self.ctx, self.fr_ctx, self.axis

        def local_step(ps, msg, px0, px1, psign, gpk, idx, live, rand):
            # decompress the [Vl, t] grid as flat lanes (what
            # blsops.threshold_recombine says of the t axis holds for the
            # square-root chain too)
            v, t = psign.shape
            flat = lambda a: a.reshape(v * t, *a.shape[2:])
            grid = lambda a: a.reshape(v, t, *a.shape[1:])
            partials, dec_ok = DEC.decompress_g2_graph(
                ctx, fr_ctx, (flat(px0), flat(px1)), flat(psign)
            )
            partials = jax.tree_util.tree_map(grid, partials)
            row_ok = jnp.logical_and(jnp.all(grid(dec_ok), axis=1), live)
            group_sig, all_ok = self._step_rlc_body(
                ps, msg, partials, gpk, idx, row_ok, rand
            )
            return group_sig, all_ok, row_ok

        sharded = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                P(axis), P(axis), P(axis),
            ),
            out_specs=(P(axis), P(), P(axis)),
        )
        return jax.jit(sharded)

    def _build_verify_dec(self):
        """Per-lane attribution verify on PARSED signature lanes:
        decompress in-program (sqrt + sign + on-curve + psi subgroup
        check), then the pairing verify — one device dispatch for the
        whole decode+verify stage."""
        ctx, fr_ctx, axis = self.ctx, self.fr_ctx, self.axis

        def local(pk, msg, sx0, sx1, sign, live):
            sig, dec_ok = DEC.decompress_g2_graph(
                ctx, fr_ctx, (sx0, sx1), sign
            )
            ok = DP.batched_verify(ctx, pk, msg, sig)
            return jnp.logical_and(jnp.logical_and(ok, dec_ok), live)

        sharded = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)
            ),
            out_specs=P(axis),
        )
        return jax.jit(sharded)

    def _build_verify_rlc_dec(self):
        """RLC verify on PARSED signature lanes, one verdict per SET:
        `seg` names each lane's segment (< VERIFY_SETS) and the RLC
        product is taken per segment (ops/pairing.batched_verify_rlc_sets:
        a Miller pair a lane, (r * pk, H(m)), and ONE a segment, (-G1,
        the segment's r * sig summed in G2) — local lanes + VERIFY_SETS
        pairs a shard, one scan) — each shard judges its own lanes of a
        set under its own exponents and against its own sum, the
        cross-device op is a psum of per-segment failure counts.
        Undecodable lanes get exponent 0 (the identity on both sides:
        neutral in their set's product and its sum) and come back False
        in the per-lane mask output; a set's verdict therefore means
        'every lane of it that DECODED verified' — the host resolves a
        lane as decode_mask AND its set's verdict. Decompression keeps
        its subgroup check: the pairing is bilinear on the summed point
        only for signatures in the r-torsion subgroup."""
        ctx, fr_ctx, axis = self.ctx, self.fr_ctx, self.axis
        n_sets = self.VERIFY_SETS

        def local(pk, msg, sx0, sx1, sign, live, rand, seg):
            sig, dec_ok = DEC.decompress_g2_graph(
                ctx, fr_ctx, (sx0, sx1), sign
            )
            lane_ok = jnp.logical_and(dec_ok, live)
            rand_live = jnp.where(lane_ok[:, None], rand, 0)
            ok = DP.batched_verify_rlc_sets(
                ctx, fr_ctx, pk, msg, sig, rand_live, seg, n_sets
            )
            bad = jax.lax.psum(jnp.logical_not(ok).astype(jnp.int32), axis)
            return bad == 0, lane_ok

        sharded = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                P(axis), P(axis),
            ),
            out_specs=(P(), P(axis)),
        )
        return jax.jit(sharded)

    def _build_h2c(self):
        """Sharded device hash-to-curve tail: hash_to_field outputs in,
        cleared G2 points out (ops/sswu.hash_to_g2_graph). The bulk
        message-cache warm-up program."""
        ctx, fr_ctx, axis = self.ctx, self.fr_ctx, self.axis

        def local(u00, u01, u10, u11, s0, s1, live):
            aff, valid = SSWU.hash_to_g2_graph(
                ctx, fr_ctx, (u00, u01), (u10, u11), s0, s1
            )
            return aff, jnp.logical_and(valid, live)

        sharded = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                P(axis),
            ),
            out_specs=(P(axis), P(axis)),
        )
        return jax.jit(sharded)

    def _build_g1dec(self):
        """Sharded batched G1 decompression (GLV subgroup check) — the
        bulk pubkey-cache warm-up program."""
        ctx, fr_ctx, axis = self.ctx, self.fr_ctx, self.axis

        def local(x0, sign, inf, ok, live):
            aff, valid = DEC.decompress_g1_graph(
                ctx, fr_ctx, x0, sign, inf, ok
            )
            return aff, jnp.logical_and(valid, live)

        sharded = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
        )
        return jax.jit(sharded)

    def _build_verify(self):
        """Plain per-lane sharded verify: ok[N] — the attribution path
        (each lane pays its own final exponentiation; used only when the
        RLC fast path says the batch contains a failure)."""
        ctx, axis = self.ctx, self.axis

        def local(pk, msg, sig, live):
            ok = DP.batched_verify(ctx, pk, msg, sig)
            return jnp.logical_and(ok, live)

        sharded = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
        )
        return jax.jit(sharded)

    def _build_verify_rlc(self):
        """Sharded whole-batch RLC verify: every shard product-trees its
        lanes under independent 64-bit exponents and runs ONE local final
        exponentiation; the cross-device op is a scalar psum of failure
        counts. Padding lanes (live=False) get exponent 0 so their
        pairing values contribute ^0 = 1."""
        ctx, fr_ctx, axis = self.ctx, self.fr_ctx, self.axis

        def local(pk, msg, sig, live, rand):
            rand = jnp.where(live[:, None], rand, 0)
            ok = DP.batched_verify_rlc(ctx, fr_ctx, pk, msg, sig, rand)
            bad = jax.lax.psum(jnp.logical_not(ok).astype(jnp.int32), axis)
            return bad == 0

        sharded = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(),
        )
        return jax.jit(sharded)

    def step_rlc(self, pubshares, msg, partials, group_pk, indices, live, rand):
        """Fast path: (group_sig, all_ok). `rand` is a [V] raw Fr limb
        array of independent nonzero 64-bit exponents (host randomness,
        one a row — see make_rand)."""
        return self._step_rlc(
            pubshares, msg, partials, group_pk, indices, live, rand
        )

    def make_rand(self, v: int, rng=None) -> jnp.ndarray:
        """[V_padded] independent nonzero 64-bit exponents packed as raw
        Fr limbs, one a recombine row (the row's one pairing lane).
        Defaults to OS randomness (SystemRandom) — the 2^-64 soundness
        bound assumes exponents unpredictable to the signers; pass a
        seeded Random only in tests."""
        return self.make_lane_rand(v, rng=rng)

    # -- host-facing ------------------------------------------------------

    def shard_count(self) -> int:
        return self.mesh.devices.size

    def bucket_lanes(self, n: int) -> int:
        """Padded batch size for n lanes: the shared power-of-two bucket
        ladder (ops/blsops.bucket_lanes), kept divisible by the mesh so
        shard_map splits evenly. One ladder across BlsEngine and this
        plane bounds jit-cache growth to O(log max_batch) shapes."""
        return blsops.bucket_lanes(n, self.shard_count())

    def jit_cache_size(self) -> int:
        """Compiled-program count across this plane's programs (point
        AND decode-fused families) — the bucket-discipline regression
        signal (see blsops counterpart)."""
        return sum(
            prog._cache_size()
            for prog in (
                self._step,
                self._step_rlc,
                self._verify,
                self._verify_rlc,
                self._step_dec,
                self._step_rlc_dec,
                self._verify_dec,
                self._verify_rlc_dec,
                self._h2c,
                self._g1dec,
            )
        )

    # -- bulk warm-up host API (ISSUE 6) ----------------------------------

    def hash_to_g2_host(self, msgs, dst: bytes = SSWU.DST_POP):
        """Messages (raw bytes or sswu.HashedMsg lanes) -> ([affine G2
        point], [valid]) through the sharded device SSWU program; the
        host pays only SHA-256 hash_to_field. Bucket-padded like every
        other entry point, so warm-up chunks reuse compiled programs."""
        lanes = [
            m
            if isinstance(m, SSWU.HashedMsg)
            else SSWU.hash_to_field_lane(m, dst)
            for m in msgs
        ]
        n = len(lanes)
        if n == 0:
            return [], []
        pad = self.bucket_lanes(n) - n
        lanes = lanes + [lanes[0]] * pad
        arrays = SSWU.pack_hashed(self.ctx, lanes)
        live = jnp.asarray(np.arange(n + pad) < n)

        def run():
            aff, valid = self._h2c(*arrays, live)
            return (
                C.g2_unpack(self.ctx, aff)[:n],
                [bool(b) for b in np.asarray(valid)[:n]],
            )

        return self._timed("h2c", n, run)

    def decompress_g1_host(self, encoded):
        """Compressed 48-byte G1 lanes (or parsed lanes) -> ([affine
        point | None], [valid]) through the sharded decompression
        program — per-lane masks, never exceptions."""
        parsed = [
            p if isinstance(p, DEC.ParsedPoint) else DEC.parse_g1_lane(p)
            for p in encoded
        ]
        n = len(parsed)
        if n == 0:
            return [], []
        pad = self.bucket_lanes(n) - n
        parsed = parsed + [parsed[0]] * pad
        x0, sign, inf, ok = DEC.pack_parsed_g1(self.ctx, parsed)
        live = jnp.asarray(np.arange(n + pad) < n)

        def run():
            aff, valid = self._g1dec(x0, sign, inf, ok, live)
            return (
                C.g1_unpack(self.ctx, aff)[:n],
                [bool(b) for b in np.asarray(valid)[:n]],
            )

        return self._timed("g1dec", n, run)

    def pack_inputs(self, pubshares, msgs, partials, group_pks, indices):
        """Python-int affine points -> device arrays laid out [V, t]/[V].

        V is padded up to the power-of-two bucket ladder (bucket_lanes)
        by repeating lane 0; padding lanes carry live=False and are
        excluded from the psum total (and sliced off by step_host)."""
        v = len(msgs)
        t = self.t
        pad = self.bucket_lanes(v) - v
        if pad:
            pubshares = list(pubshares) + [pubshares[0]] * pad
            msgs = list(msgs) + [msgs[0]] * pad
            partials = list(partials) + [partials[0]] * pad
            group_pks = list(group_pks) + [group_pks[0]] * pad
            indices = list(indices) + [indices[0]] * pad
        vp = v + pad
        flat_ps = [p for row in pubshares for p in row]
        flat_sig = [s for row in partials for s in row]
        ps = C.g1_pack(self.ctx, flat_ps)
        ps = jax.tree_util.tree_map(lambda a: a.reshape(vp, t, -1), ps)
        sig = C.g2_pack(self.ctx, flat_sig)
        sig = jax.tree_util.tree_map(lambda a: a.reshape(vp, t, -1), sig)
        msg = C.g2_pack(self.ctx, msgs)
        gpk = C.g1_pack(self.ctx, group_pks)
        idx = jnp.asarray(np.asarray(indices, np.int32))
        live = jnp.asarray(np.arange(vp) < v)
        return ps, msg, sig, gpk, idx, live

    def step(self, pubshares, msg, partials, group_pk, indices, live):
        """Run one slot step on packed inputs. Returns (group_sig, ok,
        total_ok) device values."""
        return self._step(pubshares, msg, partials, group_pk, indices, live)

    def step_host(self, pubshares, msgs, partials, group_pks, indices):
        """Convenience host-level wrapper (pack, run, unpack)."""
        v = len(msgs)
        args = self.pack_inputs(pubshares, msgs, partials, group_pks, indices)
        group_sig, ok, total = self._step(*args)
        return (
            C.g2_unpack(self.ctx, group_sig)[:v],
            [bool(b) for b in np.asarray(ok)[:v]],
            int(total),
        )

    # -- coalescer-facing host API ----------------------------------------
    # (core/cryptoplane.SlotCoalescer talks to the plane exclusively
    # through recombine_host / verify_host so a counting fake can stand
    # in for the device in fast-tier tests)

    def pack_verify_inputs(self, pks, msgs, sigs):
        """Python-int affine points -> [N] device arrays + live mask,
        N padded up to the power-of-two bucket ladder by repeating
        lane 0."""
        n = len(pks)
        pad = self.bucket_lanes(n) - n
        if pad:
            pks = list(pks) + [pks[0]] * pad
            msgs = list(msgs) + [msgs[0]] * pad
            sigs = list(sigs) + [sigs[0]] * pad
        pk = C.g1_pack(self.ctx, pks)
        msg = C.g2_pack(self.ctx, msgs)
        sig = C.g2_pack(self.ctx, sigs)
        live = jnp.asarray(np.arange(n + pad) < n)
        return pk, msg, sig, live

    def make_lane_rand(self, n: int, rng=None) -> jnp.ndarray:
        """[N_padded] independent nonzero 64-bit exponents as raw Fr
        limbs (see make_rand for the randomness contract)."""
        import random as _random

        rng = rng or _random.SystemRandom()
        np_ = self.bucket_lanes(n)
        return jnp.asarray(
            np.asarray(
                [
                    limb.int_to_limbs(
                        rng.randrange(1, 1 << 64),
                        self.fr_ctx.n_limbs,
                        self.fr_ctx.limb_bits,
                        self.fr_ctx.np_dtype,
                    )
                    for _ in range(np_)
                ]
            )
        )

    def pack_verify_inputs_parsed(self, pks, msgs, parsed, sets=None):
        """Decode-mode-device pack: pk/msg POINTS (host-cached decodes)
        plus PARSED compressed signature lanes
        (ops/decompress.ParsedPoint, host-valid and finite — the
        coalescer prefails the rest). Same bucket padding and trailing
        live mask as pack_verify_inputs; in front of the arrays ride the
        lanes' sets (LaneSets): `sets` is one label per lane, equal
        labels for the lanes of one partial-signature set (the
        coalescer's verify jobs), None for a caller that names none —
        then the batch is one segment and judged as a whole."""
        n = len(pks)
        pad = self.bucket_lanes(n) - n
        if pad:
            pks = list(pks) + [pks[0]] * pad
            msgs = list(msgs) + [msgs[0]] * pad
            parsed = list(parsed) + [parsed[0]] * pad
        pk = C.g1_pack(self.ctx, pks)
        msg = C.g2_pack(self.ctx, msgs)
        sx0, sx1, sign, _inf, _ok = DEC.pack_parsed_g2(self.ctx, parsed)
        live = jnp.asarray(np.arange(n + pad) < n)
        return self._lane_sets(sets, n, pad), pk, msg, sx0, sx1, sign, live

    def _lane_sets(self, sets, n: int, pad: int) -> LaneSets:
        """Fold a batch's sets into the program's VERIFY_SETS segments:
        one segment a set while they fit, neighbouring sets sharing a
        segment beyond that. Padding lanes ride segment 0 (their
        exponent is 0)."""
        s = self.VERIFY_SETS
        seg = np.zeros(n + pad, np.int32)
        if sets is None:
            # whatever sets segment 0 holds, nobody named them
            return LaneSets(seg, np.arange(s) == 0)
        ids = np.unique(np.asarray(sets), return_inverse=True)[1]
        count = int(ids.max()) + 1
        segment_of = np.arange(count) * s // max(count, s)  # set -> segment
        seg[:n] = segment_of[ids]
        return LaneSets(seg, np.bincount(segment_of, minlength=s) > 1)

    def verify_packed_parsed(self, arrays, rand, n: int) -> list[bool | None]:
        """Device stage for a parsed verify batch: decompression is fused
        into the verify program (no separate decode dispatch), and the
        RLC tier answers per SET: a lane is True iff it decoded AND its
        set's product is 1. A lane that decoded in a set whose product
        is not 1 is None — refused with its set, not judged apart: the
        RLC tier cannot say which lane of a failing set is at fault, and
        a set is dropped whole by its submitter on one bad lane, so
        nobody needs it said. False is a lane KNOWN bad: it did not
        decode, or the per-lane program refused it — the tier behind
        this one, for a failing segment that holds more than one set
        (more sets than VERIFY_SETS, or none named)."""
        sets, pk, msg, sx0, sx1, sign, live = arrays

        def fast():
            set_ok, lane_ok = self._verify_rlc_dec(
                pk, msg, sx0, sx1, sign, live, rand, sets.seg
            )
            return np.asarray(set_ok), np.asarray(lane_ok)

        set_ok, lane_ok = self._timed("verify_rlc_dec", n, fast)
        if not np.any(~set_ok & sets.mixed):
            return [
                False if not decoded else True if passed else None
                for decoded, passed in zip(lane_ok[:n], set_ok[sets.seg[:n]])
            ]
        ok = self._timed(
            "verify_dec",
            n,
            lambda: np.asarray(
                self._verify_dec(pk, msg, sx0, sx1, sign, live)
            ),
        )
        return [bool(b) for b in ok[:n]]

    def verify_packed(self, arrays, rand, n: int) -> list[bool]:
        """Device stage of verify_host on an already-packed batch — the
        coalescer's pipelined flush packs on its decode pool and calls
        this from the serialized device lane, so host packing of window
        k overlaps device execution of window k-1."""
        pk, msg, sig, live = arrays
        if self._timed(
            "verify_rlc",
            n,
            lambda: bool(self._verify_rlc(pk, msg, sig, live, rand)),
        ):
            return [True] * n
        ok = self._timed(
            "verify",
            n,
            lambda: np.asarray(self._verify(pk, msg, sig, live)),
        )
        return [bool(b) for b in ok[:n]]

    def verify_host(self, pks, msgs, sigs, rng=None) -> list[bool]:
        """Sharded batch verify of N independent (pk, msg, sig) lanes.
        RLC fast path first (one shared final-exp per shard); only a
        failing batch pays the per-lane attribution program."""
        n = len(pks)
        if n == 0:
            return []
        arrays = self.pack_verify_inputs(pks, msgs, sigs)
        rand = self.make_lane_rand(n, rng=rng)
        return self.verify_packed(arrays, rand, n)

    def pack_inputs_parsed(
        self, pubshares, msgs, parsed_partials, group_pks, indices
    ):
        """Decode-mode-device recombine pack: [V, t] PARSED partial
        signatures ride as raw limb grids; everything else is points as
        in pack_inputs."""
        v = len(msgs)
        t = self.t
        pad = self.bucket_lanes(v) - v
        if pad:
            pubshares = list(pubshares) + [pubshares[0]] * pad
            msgs = list(msgs) + [msgs[0]] * pad
            parsed_partials = (
                list(parsed_partials) + [parsed_partials[0]] * pad
            )
            group_pks = list(group_pks) + [group_pks[0]] * pad
            indices = list(indices) + [indices[0]] * pad
        vp = v + pad
        flat_ps = [p for row in pubshares for p in row]
        ps = C.g1_pack(self.ctx, flat_ps)
        ps = jax.tree_util.tree_map(lambda a: a.reshape(vp, t, -1), ps)
        flat_parsed = [p for row in parsed_partials for p in row]
        px0, px1, psign, _inf, _ok = DEC.pack_parsed_g2(
            self.ctx, flat_parsed
        )
        px0 = px0.reshape(vp, t, -1)
        px1 = px1.reshape(vp, t, -1)
        psign = psign.reshape(vp, t)
        msg = C.g2_pack(self.ctx, msgs)
        gpk = C.g1_pack(self.ctx, group_pks)
        idx = jnp.asarray(np.asarray(indices, np.int32))
        live = jnp.asarray(np.arange(vp) < v)
        return ps, msg, px0, px1, psign, gpk, idx, live

    def recombine_packed_parsed(self, args, rand, v: int):
        """Device stage for a parsed recombine batch. Rows with an
        undecodable partial recombine as identities (their group sig
        unpacks to None) and come back ok=False."""
        def fast():
            group_sig, all_ok, row_ok = self._step_rlc_dec(*args, rand)
            if not bool(all_ok):
                return None
            return (
                C.g2_unpack(self.ctx, group_sig)[:v],
                [bool(b) for b in np.asarray(row_ok)[:v]],
            )

        res = self._timed("step_rlc_dec", v, fast)
        if res is not None:
            return res

        def attrib():
            group_sig, ok, _total = self._step_dec(*args)
            return (
                C.g2_unpack(self.ctx, group_sig)[:v],
                [bool(b) for b in np.asarray(ok)[:v]],
            )

        return self._timed("step_dec", v, attrib)

    def recombine_packed(self, args, rand, v: int):
        """Device stage of recombine_host on an already-packed [V, t]
        batch (see verify_packed for the pipelining contract)."""
        def fast():
            group_sig, all_ok = self.step_rlc(*args, rand)
            if not bool(all_ok):
                return None
            return C.g2_unpack(self.ctx, group_sig)[:v], [True] * v

        res = self._timed("step_rlc", v, fast)
        if res is not None:
            return res

        def attrib():
            group_sig, ok, _total = self.step(*args)
            return (
                C.g2_unpack(self.ctx, group_sig)[:v],
                [bool(b) for b in np.asarray(ok)[:v]],
            )

        return self._timed("step", v, attrib)

    def recombine_host(
        self, pubshares, msgs, partials, group_pks, indices, rng=None
    ):
        """Recombine + verify [V, t] threshold workloads in one sharded
        program: returns ([V] group signature points, [V] ok flags).
        RLC fast path first; a failing batch re-runs the per-lane step
        for attribution."""
        v = len(msgs)
        if v == 0:
            return [], []
        args = self.pack_inputs(pubshares, msgs, partials, group_pks, indices)
        rand = self.make_rand(v, rng=rng)
        return self.recombine_packed(args, rand, v)

    # -- analyzer registration (ISSUE 11) ---------------------------------

    def kernel_families(self, prefix: str = "mesh"):
        """This plane's program variants as named kernel families for the
        static analyzer (charon_tpu/analysis/jaxpr_check.py): build
        closures pack canonical generator-point inputs on the bucket
        ladder and return (program, args) pairs that jax.make_jaxpr can
        trace WITHOUT executing. Returns {name: blsops.KernelFamily}."""
        import random as _random

        from charon_tpu.crypto.g1g2 import G1_GEN, G2_GEN, g2_to_bytes

        t = self.t
        n = self.bucket_lanes(4)
        mult = self.shard_count()
        idx_row = list(range(1, t + 1))
        rng = _random.Random(0)  # shape-only tracing — values never run

        def spec(fn, args):
            return blsops.TraceSpec(fn, args, self.ctx, n, mult)

        def _points():
            return (
                [[G1_GEN] * t] * n,
                [G2_GEN] * n,
                [[G2_GEN] * t] * n,
                [G1_GEN] * n,
                [idx_row] * n,
            )

        def _step():
            return spec(self._step, self.pack_inputs(*_points()))

        def _step_rlc():
            return spec(
                self._step_rlc,
                (*self.pack_inputs(*_points()), self.make_rand(n, rng=rng)),
            )

        def _verify():
            args = self.pack_verify_inputs(
                [G1_GEN] * n, [G2_GEN] * n, [G2_GEN] * n
            )
            return spec(self._verify, args)

        def _verify_rlc():
            args = self.pack_verify_inputs(
                [G1_GEN] * n, [G2_GEN] * n, [G2_GEN] * n
            )
            return spec(
                self._verify_rlc, (*args, self.make_lane_rand(n, rng=rng))
            )

        def _parsed():
            return DEC.parse_g2_lane(g2_to_bytes(G2_GEN))

        def _verify_dec():
            _sets, *args = self.pack_verify_inputs_parsed(
                [G1_GEN] * n, [G2_GEN] * n, [_parsed()] * n
            )
            return spec(self._verify_dec, tuple(args))

        def _verify_rlc_dec():
            sets, *args = self.pack_verify_inputs_parsed(
                [G1_GEN] * n, [G2_GEN] * n, [_parsed()] * n
            )
            return spec(
                self._verify_rlc_dec,
                (*args, self.make_lane_rand(n, rng=rng), sets.seg),
            )

        def _parsed_points():
            return (
                [[G1_GEN] * t] * n,
                [G2_GEN] * n,
                [[_parsed()] * t] * n,
                [G1_GEN] * n,
                [idx_row] * n,
            )

        def _step_dec():
            return spec(self._step_dec, self.pack_inputs_parsed(*_parsed_points()))

        def _step_rlc_dec():
            return spec(
                self._step_rlc_dec,
                (
                    *self.pack_inputs_parsed(*_parsed_points()),
                    self.make_rand(n, rng=rng),
                ),
            )

        def _h2c():
            lanes = [
                SSWU.hash_to_field_lane(b"jaxpr-check", SSWU.DST_POP)
            ] * n
            live = jnp.asarray(np.ones(n, bool))
            return spec(self._h2c, (*SSWU.pack_hashed(self.ctx, lanes), live))

        def _g1dec():
            from charon_tpu.crypto.g1g2 import g1_to_bytes

            parsed = [DEC.parse_g1_lane(g1_to_bytes(G1_GEN))] * n
            live = jnp.asarray(np.ones(n, bool))
            return spec(
                self._g1dec, (*DEC.pack_parsed_g1(self.ctx, parsed), live)
            )

        builders = {
            "step": (_step, False),
            "step_rlc": (_step_rlc, False),
            "verify": (_verify, False),
            "verify_rlc": (_verify_rlc, False),
            "verify_dec": (_verify_dec, False),
            "verify_rlc_dec": (_verify_rlc_dec, False),
            "step_dec": (_step_dec, False),
            "step_rlc_dec": (_step_rlc_dec, False),
            # the warm-up programs are lighter than the pairing bodies
            # but still SSWU/sqrt chains — h2c stays digest-covered,
            # g1dec is cheap enough to sentinel every run
            "h2c": (_h2c, False),
            "g1dec": (_g1dec, True),
        }
        return {
            f"{prefix}/{fname}": blsops.KernelFamily(
                f"{prefix}/{fname}", build, sentinel
            )
            for fname, (build, sentinel) in builders.items()
        }


    # canonical duty shapes: lane 1 catches the SMALLEST bucket (a lone
    # first-slot submission pads to the shard count, not to 16), the
    # rest cover the burst sizes; duplicates after bucket-padding are
    # compiled once (e.g. 1 and 16 share bucket 16 on a 16-shard mesh)
    PREWARM_VERIFY_LANES = (1, 16, 64, 256)
    PREWARM_RECOMBINE_LANES = (1, 16, 64)

    def prewarm_programs(
        self,
        verify_lanes=None,
        recombine_lanes=None,
        decompress: bool = False,
    ) -> list[tuple[str, str, int, "callable"]]:
        """The programs `prewarm` compiles, one entry each:
        [(kind, family, bucket_lanes, run)] where `run()` packs
        generator-point dummies for that bucket, dispatches the ONE
        program `family` names (same names as kernel_families /
        on_program, minus the "mesh/" prefix) and syncs its result.
        Callers that cannot afford every tier (a cold boot that only
        ever lands on the RLC fast path) pick the families they need;
        `prewarm` runs them all."""
        from charon_tpu.crypto.g1g2 import G1_GEN, G2_GEN, g2_to_bytes

        if verify_lanes is None:
            verify_lanes = self.PREWARM_VERIFY_LANES
        if recombine_lanes is None:
            recombine_lanes = self.PREWARM_RECOMBINE_LANES
        verify_lanes = _dedupe_buckets(verify_lanes, self.bucket_lanes)
        recombine_lanes = _dedupe_buckets(recombine_lanes, self.bucket_lanes)
        t = self.t
        idx_row = list(range(1, t + 1))
        # generator-point encodings: decompression takes the live
        # (finite, subgroup-valid) path through the sqrt chain
        gen_parsed = DEC.parse_g2_lane(g2_to_bytes(G2_GEN))

        def verify_args(n):
            return (
                *self.pack_verify_inputs(
                    [G1_GEN] * n, [G2_GEN] * n, [G2_GEN] * n
                ),
                self.make_lane_rand(n),
            )

        def verify_dec_args(n):
            # what a live flush dispatches (verify_packed_parsed), to
            # the dtype: the arrays, the exponents, the segment ids
            sets, *arrays = self.pack_verify_inputs_parsed(
                [G1_GEN] * n, [G2_GEN] * n, [gen_parsed] * n
            )
            return (*arrays, self.make_lane_rand(n), sets.seg)

        def step_args(v):
            return (
                *self.pack_inputs(
                    [[G1_GEN] * t] * v,
                    [G2_GEN] * v,
                    [[G2_GEN] * t] * v,
                    [G1_GEN] * v,
                    [idx_row] * v,
                ),
                self.make_rand(v),
            )

        def step_dec_args(v):
            return (
                *self.pack_inputs_parsed(
                    [[G1_GEN] * t] * v,
                    [G2_GEN] * v,
                    [[gen_parsed] * t] * v,
                    [G1_GEN] * v,
                    [idx_row] * v,
                ),
                self.make_rand(v),
            )

        # (kind, lanes, args builder, [(family, program, trailing
        # arguments it does not take)]): each shape compiles BOTH tiers
        # — the RLC fast path AND the per-lane attribution program,
        # which takes no exponents (nor segment ids)
        groups = [
            ("verify", verify_lanes, verify_args,
             [("verify_rlc", self._verify_rlc, 0),
              ("verify", self._verify, 1)]),
            ("recombine", recombine_lanes, step_args,
             [("step_rlc", self._step_rlc, 0),
              ("step", self._step, 1)]),
        ]
        if decompress:
            # decode-fused programs (decode_mode device): same buckets
            groups += [
                ("verify-dec", verify_lanes, verify_dec_args,
                 [("verify_rlc_dec", self._verify_rlc_dec, 0),
                  ("verify_dec", self._verify_dec, 2)]),
                ("recombine-dec", recombine_lanes, step_dec_args,
                 [("step_rlc_dec", self._step_rlc_dec, 0),
                  ("step_dec", self._step_dec, 1)]),
            ]

        def runner(build, prog, drop, n):
            def run():
                args = build(n)
                jax.block_until_ready(prog(*args[: len(args) - drop]))

            return run

        return [
            (kind, family, self.bucket_lanes(n),
             runner(build, prog, drop, n))
            for kind, lanes, build, tiers in groups
            for n in lanes
            for family, prog, drop in tiers
        ]

    def prewarm(
        self,
        verify_lanes=None,
        recombine_lanes=None,
        decompress: bool = False,
    ) -> list[tuple[str, int, float]]:
        """Trace + compile the canonical duty shapes up front so the
        first live slot never eats a cold pairing compile on the duty
        path (XLA pairing programs compile in minutes cold).

        Each shape compiles BOTH tiers EXPLICITLY — the RLC fast path
        AND the per-lane attribution program (generator-point dummies
        are valid triples, so the RLC early-return would otherwise skip
        the attribution tier and the first forged lane mid-slot would
        still eat a cold compile). Shapes land on the same bucket
        ladder live flushes pad to, deduplicated per bucket. Returns
        [(kind, bucket_lanes, seconds)] per compiled shape (both tiers'
        seconds summed; prewarm_programs has the per-program split).

        app/run.py sequences this AFTER core/autotune.resolve so the
        programs compile under the TUNED KernelConfig routing (and,
        warm, replay as persistent-cache loads — the AOT artifact
        story); the tuner's prewarm ladder (autotune.PREWARM_LANES)
        deliberately matches these shapes."""
        seconds: dict[tuple[str, int], float] = {}
        for kind, _family, bucket, run in self.prewarm_programs(
            verify_lanes, recombine_lanes, decompress
        ):
            t0 = time.monotonic()
            run()
            seconds[kind, bucket] = (
                seconds.get((kind, bucket), 0.0) + time.monotonic() - t0
            )
        return [(kind, bucket, s) for (kind, bucket), s in seconds.items()]


_ANALYSIS_PLANE_T = 3  # canonical threshold for the analyzer's plane


def register_analysis_families(
    mesh: Mesh | None = None, t: int = _ANALYSIS_PLANE_T
) -> "SlotCryptoPlane":
    """Build the canonical analysis plane (single-device by default —
    the program structure is shard-count-invariant; shard_map only
    changes the split) and register its program variants into the
    blsops kernel-family registry. Idempotent. Called by
    analysis/jaxpr_check.py and core/cryptoplane.kernel_inventory()."""
    mesh = mesh or make_mesh(jax.devices()[:1])
    plane = SlotCryptoPlane(mesh, t)
    for name, fam in plane.kernel_families().items():
        if name not in blsops.kernel_families():
            blsops.register_kernel_family(name, fam.build, fam.sentinel)
    return plane
