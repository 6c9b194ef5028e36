"""Batched multi-limb Montgomery arithmetic for big prime fields on TPU.

Representation: an element of Z/m is a little-endian vector of `n_limbs`
limbs of `limb_bits` bits stored as `dtype`, shape (..., n_limbs); leading
axes are batch axes. All public ops accept arbitrary broadcastable batch
shapes and keep values fully reduced (< m).

Two limb geometries are provided, selected per ModCtx:

  * 24-bit limbs in uint64 (CPU-friendly): products of two limbs are
    < 2^48, so a full 16-term schoolbook column plus Montgomery additions
    stays < 2^54 — far from uint64 overflow, which means NO carry
    normalization is needed inside the hot loops (one carry pass at the
    end of a multiply). 24 bits = 3 bytes, so host packing is a pure-numpy
    byte reshuffle.
  * 12-bit limbs in uint32 (TPU-friendly): TPUs have no native 64-bit
    integers (XLA emulates them slowly), so the TPU contexts use 12-bit
    limbs whose products fit 24 bits; a 32-term column plus Montgomery
    additions stays < 2^31 in uint32. The 12-bit width also splits into
    two 6-bit pieces that fit SIGNED int8 — the MXU decomposition of the
    constant-operand convolutions lives in ops/limb_mxu.py.

The no-mid-loop-carry invariant (see mont_mul) is asserted in make_ctx for
whatever geometry is requested.

Montgomery domain: R = 2^(limb_bits * n_limbs). `mont_mul(a, b) =
a*b*R^-1 mod m`. Values enter the domain with `to_mont` (device) and leave
with `from_mont`.

This file is generic over the modulus (instantiated for BLS12-381 Fp and Fr
at the bottom) and is the device-side counterpart of
charon_tpu/crypto/fields.py, which serves as its correctness oracle.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from charon_tpu.crypto.fields import P, R as FR_MOD

# Default geometry (kept as module constants for the host packing helpers).
LIMB_BITS = 24
LIMB_BYTES = 3
MASK = (1 << LIMB_BITS) - 1


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: identity hash, so
# module-singleton contexts work as lru_cache / static-argnum keys despite
# holding numpy arrays.
class ModCtx:
    """Everything the device needs to do arithmetic mod `modulus`."""

    name: str
    modulus: int
    n_limbs: int
    limb_bits: int
    np_dtype: type  # np.uint64 | np.uint32
    limbs: np.ndarray  # (n_limbs,) — the modulus
    pinv: int  # -modulus^-1 mod 2^limb_bits
    ninv: np.ndarray  # (n_limbs,) — -modulus^-1 mod 2^(limb_bits*n_limbs)
    r2: np.ndarray  # (n_limbs,) — R^2 mod m (to_mont multiplier)
    mont_one: np.ndarray  # (n_limbs,) — R mod m (1 in Montgomery form)

    @property
    def mask(self) -> int:
        return (1 << self.limb_bits) - 1

    @property
    def dtype(self):
        return jnp.dtype(self.np_dtype)

    @property
    def r_mont(self) -> int:
        return (1 << (self.limb_bits * self.n_limbs)) % self.modulus

    def u(self, x: int):
        """Python int -> dtype scalar constant."""
        return jnp.asarray(x, self.dtype)


def int_to_limbs(x: int, n_limbs: int, limb_bits: int = LIMB_BITS, np_dtype=np.uint64) -> np.ndarray:
    out = np.empty(n_limbs, np_dtype)
    mask = (1 << limb_bits) - 1
    for i in range(n_limbs):
        out[i] = (x >> (limb_bits * i)) & mask
    return out


def make_ctx(name: str, modulus: int, n_limbs: int, limb_bits: int = LIMB_BITS, np_dtype=np.uint64) -> ModCtx:
    if modulus.bit_length() > limb_bits * n_limbs - 2:
        raise ValueError("need >= 2 bits of headroom above the modulus")
    # No-mid-loop-carry invariant: a schoolbook column of n products plus n
    # Montgomery additions plus carries must fit the accumulator dtype.
    acc_bits = np.dtype(np_dtype).itemsize * 8
    worst = 2 * n_limbs * ((1 << limb_bits) - 1) ** 2 + (1 << acc_bits - 1) // (1 << limb_bits)
    if worst >= 1 << acc_bits:
        raise ValueError(f"limb geometry {limb_bits}b x {n_limbs} overflows {acc_bits}-bit accumulator")
    r = 1 << (limb_bits * n_limbs)
    return ModCtx(
        name=name,
        modulus=modulus,
        n_limbs=n_limbs,
        limb_bits=limb_bits,
        np_dtype=np_dtype,
        limbs=int_to_limbs(modulus, n_limbs, limb_bits, np_dtype),
        pinv=(-pow(modulus, -1, 1 << limb_bits)) % (1 << limb_bits),
        ninv=int_to_limbs(
            (-pow(modulus, -1, r)) % r, n_limbs, limb_bits, np_dtype
        ),
        r2=int_to_limbs(r * r % modulus, n_limbs, limb_bits, np_dtype),
        mont_one=int_to_limbs(r % modulus, n_limbs, limb_bits, np_dtype),
    )


# ---------------------------------------------------------------------------
# Host <-> device packing (pure numpy)
# ---------------------------------------------------------------------------


def bytes_to_limbs_batch(
    data,
    n_limbs: int,
    limb_bits: int = LIMB_BITS,
    np_dtype=np.uint64,
    item_bytes: int | None = None,
    byteorder: str = "big",
) -> np.ndarray:
    """Concatenated fixed-width byte strings -> (N, n_limbs) limb array
    in ONE vectorized numpy pass (ISSUE 7): no per-int Python loop, no
    Python bigints. `data` is bytes/bytearray/memoryview of N *
    item_bytes, or an already-shaped (N, item_bytes) uint8 array —
    which is how compressed wire signatures flow from the socket buffer
    to device-ready limb arrays without an int detour.

    `byteorder` is the byte order of each item ("big" = wire format for
    BLS field elements). Supported geometries: 24-bit limbs (3 bytes
    per limb) and 12-bit limbs in pairs (3 bytes per 2 limbs, n_limbs
    even) — the two engine geometries; anything else falls back to a
    per-item int path."""
    if isinstance(data, np.ndarray):
        raw = np.ascontiguousarray(data, dtype=np.uint8)
        if raw.ndim != 2:
            raise ValueError("ndarray input must be (N, item_bytes)")
        item_bytes = raw.shape[1]
    else:
        if item_bytes is None:
            raise ValueError("item_bytes required for flat byte input")
        raw = np.frombuffer(data, np.uint8)
        if item_bytes == 0 or raw.size % item_bytes:
            raise ValueError("byte length not a multiple of item_bytes")
        raw = raw.reshape(-1, item_bytes)
    total_bits = n_limbs * limb_bits
    if item_bytes * 8 > total_bits + 7:
        raise ValueError(
            f"{item_bytes}-byte items overflow {n_limbs}x{limb_bits}-bit limbs"
        )
    if byteorder == "big":
        raw = raw[:, ::-1]
    elif byteorder != "little":
        raise ValueError(f"bad byteorder {byteorder!r}")
    needed = (total_bits + 7) // 8
    if needed != item_bytes:
        pad = np.zeros((raw.shape[0], needed - item_bytes), np.uint8)
        raw = np.concatenate([raw, pad], axis=1)
    raw = np.ascontiguousarray(raw)
    if limb_bits == 24:
        b = raw.reshape(-1, n_limbs, 3).astype(np.uint64)
        out = b[..., 0] | (b[..., 1] << np.uint64(8)) | (b[..., 2] << np.uint64(16))
        return out.astype(np_dtype, copy=False)
    if limb_bits == 12 and n_limbs % 2 == 0:
        b = raw.reshape(-1, n_limbs // 2, 3).astype(np.uint32)
        lo = b[..., 0] | ((b[..., 1] & 0x0F) << np.uint32(8))
        hi = (b[..., 1] >> np.uint32(4)) | (b[..., 2] << np.uint32(4))
        out = np.empty((raw.shape[0], n_limbs), np.uint32)
        out[:, 0::2] = lo
        out[:, 1::2] = hi
        return out.astype(np_dtype, copy=False)
    # uncommon geometry: per-item int fallback (correct, not hot)
    vals = [
        int.from_bytes(raw[i].tobytes(), "little")
        for i in range(raw.shape[0])
    ]
    return pack(vals, n_limbs, limb_bits, np_dtype)


def ctx_bytes_to_limbs(
    ctx: ModCtx, data, item_bytes: int | None = None, byteorder: str = "big"
) -> np.ndarray:
    return bytes_to_limbs_batch(
        data, ctx.n_limbs, ctx.limb_bits, ctx.np_dtype, item_bytes, byteorder
    )


def pack(values, n_limbs: int, limb_bits: int = LIMB_BITS, np_dtype=np.uint64) -> np.ndarray:
    """List/iterable of ints -> (N, n_limbs) limb array."""
    vals = list(values)
    nbytes = (n_limbs * limb_bits + 7) // 8
    if limb_bits == 24 or (limb_bits == 12 and n_limbs % 2 == 0):
        # one int->bytes conversion per value, then the shared
        # vectorized byte->limb pass (the 12-bit geometry used to pay
        # an O(N * n_limbs) pure-Python shift loop here)
        buf = b"".join(int(v).to_bytes(nbytes, "little") for v in vals)
        return bytes_to_limbs_batch(
            buf, n_limbs, limb_bits, np_dtype,
            item_bytes=nbytes, byteorder="little",
        )
    mask = (1 << limb_bits) - 1
    out = np.empty((len(vals), n_limbs), np_dtype)
    for r, v in enumerate(vals):
        v = int(v)
        for i in range(n_limbs):
            out[r, i] = (v >> (limb_bits * i)) & mask
    return out


def unpack(arr, limb_bits: int = LIMB_BITS) -> list[int]:
    """(..., n_limbs) limb array -> flat list of ints (C-order batch)."""
    arr = np.asarray(arr).reshape(-1, np.shape(arr)[-1])
    out = []
    for row in arr:
        v = 0
        for i, limb in enumerate(row):
            v |= int(limb) << (limb_bits * i)
        out.append(v)
    return out


def ctx_pack(ctx: ModCtx, values) -> np.ndarray:
    return pack(values, ctx.n_limbs, ctx.limb_bits, ctx.np_dtype)


def ctx_unpack(ctx: ModCtx, arr) -> list[int]:
    return unpack(arr, ctx.limb_bits)


# ---------------------------------------------------------------------------
# Parallel carry machinery (TPU-first: no sequential lax.scan over limbs)
#
# Carry propagation is the classic adder-carry problem: ripple (a scan over
# the limb axis) serializes 32-64 tiny steps, which starves the TPU's
# vector units and bloats compile time. Instead:
#   * _shift_carries: split each limb v = a + 2^b c and re-add the carries
#     one position up — a purely elementwise pass that shrinks the excess
#     by `limb_bits` per application (3 passes take any accumulator-range
#     value down to < 2^(limb_bits+1));
#   * _kogge_resolve: the final {0,1}-carry resolution via a Kogge-Stone
#     (generate, propagate) associative scan — O(log n) parallel steps.
# ---------------------------------------------------------------------------


def _shift_carries(ctx: ModCtx, t):
    """One elementwise carry pass: limbs' excess moves one position up.
    Returns (limbs, carry_out_of_top_limb)."""
    mask = ctx.u(ctx.mask)
    carry = t >> ctx.limb_bits
    shifted = jnp.concatenate(
        [jnp.zeros_like(carry[..., :1]), carry[..., :-1]], axis=-1
    )
    return (t & mask) + shifted, carry[..., -1]


def _kogge_resolve(ctx: ModCtx, t):
    """Resolve limbs in [0, 2^(limb_bits+1)) to canonical form, returning
    (limbs, carry_out). Kogge-Stone over (generate, propagate)."""
    mask = ctx.u(ctx.mask)
    g = (t >> ctx.limb_bits).astype(jnp.bool_)  # generates a carry
    p = (t & mask) == mask  # propagates an incoming carry

    def op(a, b):
        # combine prefix a (lower limbs) then b (higher limbs)
        ga, pa = a
        gb, pb = b
        return jnp.logical_or(gb, jnp.logical_and(pb, ga)), jnp.logical_and(pa, pb)

    gi, _ = lax.associative_scan(op, (g, p), axis=-1)
    # exclusive carries: carry into limb i is the combined generate of [0, i)
    c_in = jnp.concatenate(
        [jnp.zeros_like(gi[..., :1]), gi[..., :-1]], axis=-1
    )
    out = (t + c_in.astype(ctx.dtype)) & mask
    return out, gi[..., -1].astype(ctx.dtype)


def _normalize(ctx: ModCtx, t, passes: int = 3):
    """Arbitrary accumulator-range limbs -> canonical form, (limbs, carry).
    `carry` is the total overflow out of the top limb (sum of the shift
    passes' dropped carries plus the final resolved carry) — callers doing
    mod-2^(bits*width) arithmetic ignore it. `passes` must take the input
    down to < 2^(limb_bits+1) before the Kogge resolution: 3 covers full
    accumulator range; 1 suffices for sums of a few canonical values."""
    cs = []
    for _ in range(passes):
        t, c = _shift_carries(ctx, t)
        cs.append(c)
    out, c_final = _kogge_resolve(ctx, t)
    return out, sum(cs) + c_final


def _carry_pass(ctx: ModCtx, a):
    """Normalize limbs, dropping the final carry (value must fit)."""
    out, _ = _normalize(ctx, a)
    return out


@functools.lru_cache(maxsize=None)
def _one_hot0(n_limbs: int, np_dtype) -> np.ndarray:
    out = np.zeros(n_limbs, np_dtype)
    out[0] = 1
    return out


@functools.lru_cache(maxsize=None)
def _r_minus_m(ctx: ModCtx) -> np.ndarray:
    """R - modulus as limbs (R = 2^(limb_bits*n))."""
    r = 1 << (ctx.limb_bits * ctx.n_limbs)
    return int_to_limbs(r - ctx.modulus, ctx.n_limbs, ctx.limb_bits, ctx.np_dtype)




# ---------------------------------------------------------------------------
# Modular add / sub / neg / select
#
# One stacked normalize per op: the raw result and its modulus-adjusted
# twin are normalized together on a leading stack axis, then selected by
# the twin's carry-out. Compared to normalize-then-conditionally-subtract
# (two sequential normalizes), this halves the op count of the single
# hottest subgraph in the whole engine — adds/subs outnumber multiplies
# ~4:1 in the tower/pairing code. Precondition (asserted in make_ctx):
# 2*modulus < R, so a+b never carries out of the top limb on its own.
# ---------------------------------------------------------------------------


def _add_many(ctx: ModCtx, pairs, rm):
    """Batched modular adds: one stacked normalize for any number of
    independent (a, b) additions. Returns a list of canonical results."""
    if not pairs:
        return []
    # rm = R - p as limbs, made by the caller's side of _traced_once
    lanes = []
    for a, b in pairs:
        a, b = jnp.broadcast_arrays(a, b)
        s = a + b
        lanes.append(s)
        lanes.append(s + rm)  # == a + b + (R - p): carries out iff a+b >= p
    stacked = jnp.stack(jnp.broadcast_arrays(*lanes))
    out, carry = _normalize(ctx, stacked, passes=1)
    res = []
    for i in range(len(pairs)):
        raw, adj = out[2 * i], out[2 * i + 1]
        res.append(jnp.where((carry[2 * i + 1] == 1)[..., None], adj, raw))
    return res


def _sub_many(ctx: ModCtx, pairs, one0, p):
    """Batched modular subs, one stacked normalize. For canonical a, b:
    lane1 = a - b + R (carries iff a >= b), lane2 = a - b + p + R."""
    if not pairs:
        return []
    mask = ctx.u(ctx.mask)
    # one0 = 1 as limbs, p = the modulus as limbs: made by the caller's
    # side of _traced_once, like rm above
    lanes = []
    for a, b in pairs:
        a, b = jnp.broadcast_arrays(a, b)
        z = a + (mask - b) + one0  # a - b + R limbwise (no borrows)
        lanes.append(z)
        lanes.append(z + p)
    stacked = jnp.stack(jnp.broadcast_arrays(*lanes))
    out, carry = _normalize(ctx, stacked, passes=1)
    res = []
    for i in range(len(pairs)):
        raw, adj = out[2 * i], out[2 * i + 1]
        # carry on the raw lane <=> a >= b <=> no +p needed
        res.append(jnp.where((carry[2 * i] == 1)[..., None], raw, adj))
    return res


def add_mod(ctx: ModCtx, a, b):
    return _add_many(ctx, [(a, b)])[0]


def sub_mod(ctx: ModCtx, a, b):
    return _sub_many(ctx, [(a, b)])[0]


def add_mod_many(ctx: ModCtx, pairs):
    """Independent modular adds sharing ONE stacked normalize. The tower
    code groups its adds by dependency level through this (and
    sub_mod_many) — the main lever that keeps pairing programs compilable:
    every emitted normalize is a Kogge-Stone subgraph, so op count scales
    with dependency depth, not with the number of additions."""
    return _add_many(ctx, list(pairs))


def sub_mod_many(ctx: ModCtx, pairs):
    return _sub_many(ctx, list(pairs))


def addsub_mod_many(ctx: ModCtx, add_pairs, sub_pairs, rm, one0, p):
    """Adds and subs together in ONE stacked normalize (rm, one0, p: as
    in _add_many and _sub_many)."""
    add_pairs, sub_pairs = list(add_pairs), list(sub_pairs)
    if not add_pairs and not sub_pairs:
        return [], []
    mask = ctx.u(ctx.mask)
    # (this line and the next keep the lines below where they were: the
    # Pallas kernels carry their callers' line numbers into the cache key)
    lanes = []
    for a, b in add_pairs:
        a, b = jnp.broadcast_arrays(a, b)
        s = a + b
        lanes += [s, s + rm]
    for a, b in sub_pairs:
        a, b = jnp.broadcast_arrays(a, b)
        z = a + (mask - b) + one0
        lanes += [z, z + p]
    out, carry = _normalize(ctx, jnp.stack(jnp.broadcast_arrays(*lanes)), passes=1)
    res_add, res_sub = [], []
    for i in range(len(add_pairs)):
        raw, adj = out[2 * i], out[2 * i + 1]
        res_add.append(jnp.where((carry[2 * i + 1] == 1)[..., None], adj, raw))
    off = 2 * len(add_pairs)
    for i in range(len(sub_pairs)):
        raw, adj = out[off + 2 * i], out[off + 2 * i + 1]
        res_sub.append(jnp.where((carry[off + 2 * i] == 1)[..., None], raw, adj))
    return res_add, res_sub


def neg_mod(ctx: ModCtx, a):
    return sub_mod(ctx, jnp.zeros_like(a), a)


def double_mod(ctx: ModCtx, a):
    return add_mod(ctx, a, a)


def triple_mod(ctx: ModCtx, a):
    return add_mod(ctx, double_mod(ctx, a), a)


def is_zero(a):
    """Boolean mask over batch dims: element == 0 (must be reduced)."""
    return jnp.all(a == 0, axis=-1)


def select(mask, a, b):
    """Elementwise: mask ? a : b, with mask over batch dims."""
    return jnp.where(mask[..., None], a, b)


def zeros(ctx: ModCtx, batch_shape=()):
    return jnp.zeros((*batch_shape, ctx.n_limbs), ctx.dtype)


def match_vary(arr, template):
    """Give a constant-built limb array the same shard_map varying axes
    as `template` (adds template * 0 — exact for unsigned limbs, folded
    away by XLA). lax.scan under shard_map requires carry init and carry
    output to agree on varying manual axes, so constant scan inits
    (fp12_one, identity points) must inherit the inputs' axes."""
    return arr + template * jnp.zeros((), template.dtype)


def const(ctx: ModCtx, value: int, batch_shape=()):
    """Montgomery-form constant broadcast to a batch shape."""
    limbs = int_to_limbs(
        value % ctx.modulus * ctx.r_mont % ctx.modulus,
        ctx.n_limbs,
        ctx.limb_bits,
        ctx.np_dtype,
    )
    return jnp.broadcast_to(jnp.asarray(limbs), (*batch_shape, ctx.n_limbs))


# ---------------------------------------------------------------------------
# Montgomery multiplication
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _band_index(n: int, out_cols: int):
    """idx[i, k] = k - i clipped to [0, n-1], valid[i, k] = 0 <= k-i < n.

    Used to express the schoolbook product as ONE gather + ONE contraction
    instead of n scatter-adds: t[..., k] = sum_i a_i * b_{k-i}. Keeping the
    hot multiply at ~3 ops (vs ~n dynamic-update-slices) is what makes the
    pairing kernel's scan body compilable in seconds instead of minutes on
    TPU (XLA optimization time scales with scan-body op count)."""
    idx = np.zeros((n, out_cols), np.int32)
    valid = np.zeros((n, out_cols), bool)
    for i in range(n):
        for k in range(out_cols):
            j = k - i
            if 0 <= j < n:
                idx[i, k] = j
                valid[i, k] = True
    return idx, valid


def _conv(ctx: ModCtx, a, b, out_cols: int):
    """Banded product t[..., k] = sum_{i+j=k} a_i * b_j over out_cols
    columns. Column sums stay within the accumulator headroom (asserted in
    make_ctx), so no mid-loop carries."""
    n = ctx.n_limbs
    idx, valid = _band_index(n, out_cols)
    # b_shift[..., i, k] = b[..., k-i] (zero outside the band)
    b_shift = jnp.where(
        jnp.asarray(valid), b[..., jnp.asarray(idx)], ctx.u(0)
    )
    # contraction over the limb axis i: (..., i) x (..., i, k) -> (..., k)
    return jnp.einsum("...i,...ik->...k", a, b_shift)


def _conv_full(ctx: ModCtx, a, b):
    """Schoolbook product into 2n columns."""
    return _conv(ctx, a, b, 2 * ctx.n_limbs)


def _conv_low(ctx: ModCtx, a, b):
    """Low n columns of the product (mod 2^(limb_bits*n))."""
    return _conv(ctx, a, b, ctx.n_limbs)


# Pallas kernel dispatch: None = auto (on for the uint32 geometry when
# the default backend is a real TPU), True/False = forced. The fused
# kernel keeps the whole multiply in VMEM — the jnp path's band-matrix
# intermediates make it HBM-bound (see ops/pallas_mont.py).
_PALLAS_MODE: bool | None = None


def set_pallas(mode: bool | None) -> None:
    global _PALLAS_MODE
    _PALLAS_MODE = mode


def _pallas_active(ctx: ModCtx) -> bool:
    if ctx.np_dtype is not np.uint32:
        return False
    if _PALLAS_MODE is not None:
        return _PALLAS_MODE
    return _is_tpu_backend()


# int8-MXU dispatch (ops/limb_mxu.py): opt-in until measured on real TPU
# (call set_mxu(True); bench.py exposes it as BENCH_MXU=1, and the
# startup tuner owns it via core/autotune.KernelConfig — the legacy
# CHARON_MXU_MONT env toggle folds in there as an explicit override, so
# this hot path no longer reads the environment). Takes precedence over
# the Pallas kernel when enabled so the two lowerings can be A/B'd from
# the same bench invocation.
_MXU_MODE: bool | None = None


def set_mxu(mode: bool | None) -> None:
    global _MXU_MODE
    _MXU_MODE = mode


def _mxu_active(ctx: ModCtx) -> bool:
    if ctx.limb_bits != 12:
        return False
    if _MXU_MODE is not None:
        return _MXU_MODE
    return False


def mont_mul(ctx: ModCtx, a, b):
    """a * b * R^-1 mod m for reduced Montgomery-form inputs.

    Separated-operand Montgomery (TPU-first — every step parallel over the
    limb axis, no sequential reduction rounds):

        t = a * b                      (conv, 2n columns)
        m = (t mod R) * (-m^-1 mod R)  (low conv, n columns)
        s = t + m * p                  (conv + add; s ≡ 0 mod R)
        result = s / R  (high half)    (< 2m, one conditional subtract)

    Three convolutions + parallel carry normalization replace the n-round
    scan: ~10x fewer XLA ops and no serialization on the limb axis.
    """
    if _mxu_active(ctx):
        # with Pallas also active, the Toeplitz matmuls are issued from
        # inside the fused kernel (int8 pieces stay in VMEM); Pallas-off
        # keeps the XLA-level lowering as the A/B reference
        if _pallas_active(ctx):
            from charon_tpu.ops.pallas_mont import mont_mul_pallas

            return mont_mul_pallas(ctx, a, b, mxu=True)
        from charon_tpu.ops.limb_mxu import mont_mul_mxu

        return mont_mul_mxu(ctx, a, b)
    if _pallas_active(ctx):
        from charon_tpu.ops.pallas_mont import mont_mul_pallas

        return mont_mul_pallas(ctx, a, b)
    a, b = jnp.broadcast_arrays(a, b)
    n = ctx.n_limbs
    t = _conv_full(ctx, a, b)
    t, _ = _normalize(ctx, t)
    m = _conv_low(ctx, t[..., :n], jnp.asarray(ctx.ninv))
    m, _ = _normalize(ctx, m)  # mod R: top carry intentionally dropped
    s = t + _conv_full(ctx, m, jnp.asarray(ctx.limbs))
    return _mont_tail(ctx, s)


def _mont_tail(ctx: ModCtx, s):
    """Shared Montgomery tail (also used by ops/limb_mxu): s ≡ 0 mod R in
    accumulator range -> canonical high half, with the final conditional
    subtract fused into the last normalize — lane2 adds (R - p) into the
    high columns, so its carry-out says hi >= p; one stacked normalize
    replaces normalize + cond_sub."""
    n = ctx.n_limbs
    rm_hi = jnp.zeros(2 * n, ctx.np_dtype).at[n:].set(
        jnp.asarray(_r_minus_m(ctx))
    )
    stacked = jnp.stack(jnp.broadcast_arrays(s, s + rm_hi))
    out, carry = _normalize(ctx, stacked)
    return jnp.where(
        (carry[1] == 1)[..., None], out[1, ..., n:], out[0, ..., n:]
    )


def mont_sqr(ctx: ModCtx, a):
    return mont_mul(ctx, a, a)


def to_mont(ctx: ModCtx, a):
    """Raw limbs (< m) -> Montgomery form, on device."""
    return mont_mul(ctx, a, jnp.asarray(ctx.r2))


def from_mont(ctx: ModCtx, a):
    """Montgomery form -> raw limbs, on device."""
    one = jnp.zeros_like(a).at[..., 0].set(ctx.u(1))
    return mont_mul(ctx, a, one)


# ---------------------------------------------------------------------------
# Exponentiation by a static exponent (lax.scan over its bits)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _exp_bits(exponent: int):
    """MSB-first bit array of a static exponent."""
    return np.array([int(c) for c in bin(exponent)[2:]], np.uint8)


def mont_pow(ctx: ModCtx, a, exponent: int):
    """a^exponent (Montgomery in, Montgomery out), square-and-multiply as a
    scan over the (static) exponent bits."""
    if exponent == 0:
        return jnp.broadcast_to(jnp.asarray(ctx.mont_one), a.shape)
    bits = jnp.asarray(_exp_bits(exponent))

    def step(acc, bit):
        acc = mont_sqr(ctx, acc)
        mul = mont_mul(ctx, acc, a)
        return jnp.where(bit != 0, mul, acc), None

    # First bit is the leading 1: start from a directly.
    acc, _ = lax.scan(step, a, bits[1:])
    return acc


def inv_mod(ctx: ModCtx, a):
    """a^-1 via Fermat (Montgomery in/out). 0 maps to 0."""
    return mont_pow(ctx, a, ctx.modulus - 2)


# ---------------------------------------------------------------------------
# Field contexts
# ---------------------------------------------------------------------------

# CPU-friendly geometry: 24-bit limbs in uint64.
#   Fp: 381 bits -> 16 x 24 = 384 bits (3 bits headroom)
#   Fr: 255 bits -> 11 x 24 = 264 bits
FP = make_ctx("fp", P, 16)
FR = make_ctx("fr", FR_MOD, 11)

# TPU-friendly geometry: 12-bit limbs in uint32 (TPUs lack native 64-bit
# integer units; uint64 ops are emulated and slow there).
#   Fp: 32 x 12 = 384 bits; Fr: 22 x 12 = 264 bits
FP32 = make_ctx("fp32", P, 32, limb_bits=12, np_dtype=np.uint32)
FR32 = make_ctx("fr32", FR_MOD, 22, limb_bits=12, np_dtype=np.uint32)


def _is_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU. A backend that fails
    to initialise raises here: answering "not a TPU" would silently
    select the CPU limb geometry."""
    return jax.default_backend() == "tpu"


def default_fp_ctx() -> ModCtx:
    """Pick the Fp context matching the default JAX backend."""
    return FP32 if _is_tpu_backend() else FP


def default_fr_ctx() -> ModCtx:
    return FR32 if _is_tpu_backend() else FR


def pack_mont_host(ctx: ModCtx, values) -> np.ndarray:
    """Host-side convenience: ints -> Montgomery limb array (host bigint
    conversion; prefer to_mont-on-device for large batches)."""
    r = ctx.r_mont
    return ctx_pack(ctx, (v % ctx.modulus * r % ctx.modulus for v in values))


def unpack_mont_host(ctx: ModCtx, arr) -> list[int]:
    rinv = pow(ctx.r_mont, -1, ctx.modulus)
    return [v * rinv % ctx.modulus for v in ctx_unpack(ctx, arr)]


# ---------------------------------------------------------------------------
# The add/sub families above are most of a pairing program's trace: a few
# hundred calls on a few dozen distinct shapes, each some hundred equations
# through jnp's wrappers (18.7 of verify_rlc_dec@128's 27.5 s of trace +
# lowering). As inlined jits each (ctx, shapes) is traced once and later
# calls copy its equations into the outer trace. The limb constants are
# made OUTSIDE the jit, one array a call and in the order the plain
# functions made them: the outer trace hoists one constant per array
# object, and a constant captured once by a cached trace would be hoisted
# once, which is another module and so another compile-cache key. Bound
# here, at the end of the file, for the sake of the line numbers above.
# ---------------------------------------------------------------------------


def _traced_once(fn, *consts):
    core = jax.jit(fn, static_argnums=0, inline=True)

    @functools.wraps(fn)
    def call(ctx, *pairs):
        if not any(pairs):
            return fn(ctx, *pairs, *(None for _ in consts))
        made = (jnp.asarray(c(ctx)) for c in consts)
        if any(isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(pairs)):
            return core(ctx, *pairs, *made)
        # op by op on concrete limbs, as ever: a jit of its own a shape
        # would compile where nothing is being traced
        return fn(ctx, *pairs, *made)

    return call


def _one0(ctx: ModCtx) -> np.ndarray:
    return _one_hot0(ctx.n_limbs, ctx.np_dtype)


def _modulus(ctx: ModCtx) -> np.ndarray:
    return ctx.limbs


_add_many = _traced_once(_add_many, _r_minus_m)
_sub_many = _traced_once(_sub_many, _one0, _modulus)
addsub_mod_many = _traced_once(addsub_mod_many, _r_minus_m, _one0, _modulus)
