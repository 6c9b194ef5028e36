"""Fused Montgomery multiplication as a Pallas TPU kernel.

The jnp/XLA path in ops/limb.py expresses each of mont_mul's three limb
convolutions as gather + broadcast-multiply + einsum, which materializes a
(batch, n_limbs, 2*n_limbs) band tensor in HBM per convolution — measured
HBM-bound on v5e (throughput flat in batch size). This kernel fuses the
WHOLE mont_mul (schoolbook product, Montgomery folding, parallel carry
normalization, conditional subtract) into one VMEM-resident program per
batch tile: HBM traffic drops to read a, read b, write out.

Geometry: the TPU limb layout (12-bit limbs in uint32, 32 limbs for Fp,
22 for Fr — ops/limb.py FP32/FR32). The kernel is generic over the
modulus via embedded per-ctx constants, mirrors limb.mont_mul's algorithm
step for step, and is validated against it by tests/test_pallas_mont.py
(interpret mode on CPU; bit-exact on device).

Replaces (batched, fused) the role of herumi's asm field multiply
(ref: tbls/herumi.go links the C++/asm backend one call at a time).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from charon_tpu.ops.limb import ModCtx, _r_minus_m, int_to_limbs


# batch rows per grid step — (8, 128) native tiles; 256 rows x 64 cols
# of u32 = 64 KiB per scratch-sized value, far under ~16 MiB VMEM.
TILE = 256


def _shift_pass(t, nbits: int, mask):
    """One elementwise carry pass over the limb axis (cols). Returns the
    new limbs and the (rows, 1) carry out of the top limb — the final
    normalize's overflow detection needs every dropped top carry, exactly
    like limb._normalize sums them."""
    width = t.shape[1]
    carry = t >> nbits
    shifted = jnp.concatenate(
        [jnp.zeros_like(carry[:, :1]), carry[:, : width - 1]], axis=1
    )
    return (t & mask) + shifted, carry[:, width - 1 : width]


def _kogge(t, nbits: int, mask, width: int):
    """Kogge-Stone resolve of limbs in [0, 2^(nbits+1)); returns
    (canonical_limbs, carry_out as (rows, 1) u32 in {0, 1}).

    Entirely bool-free: Mosaic mis-lowers i1 vector casts, so generate/
    propagate flags are u32 0/1 values — g comes straight from the top
    bit (inputs are < 2^(nbits+1)), p from an arithmetic carry trick
    (((t & mask) + 1) >> nbits == 1 iff the limb is all-ones), and the
    combine uses bitwise | and & which are exact on 0/1 values."""
    g = t >> nbits  # in {0, 1} for inputs < 2^(nbits+1)
    p = ((t & mask) + jnp.uint32(1)) >> nbits  # 1 iff limb == mask
    shift = 1
    while shift < width:
        g_prev = jnp.concatenate(
            [jnp.zeros_like(g[:, :shift]), g[:, : width - shift]], axis=1
        )
        p_prev = jnp.concatenate(
            [jnp.zeros_like(p[:, :shift]), p[:, : width - shift]], axis=1
        )
        g = g | (p & g_prev)
        p = p & p_prev
        shift *= 2
    c_in = jnp.concatenate(
        [jnp.zeros_like(g[:, :1]), g[:, : width - 1]], axis=1
    )
    out = (t + c_in) & mask
    return out, g[:, width - 1 : width]


def _normalize(t, nbits: int, mask, width: int):
    """Canonicalize; returns (limbs, total_carry_out as (rows, 1) u32)."""
    t, c1 = _shift_pass(t, nbits, mask)
    t, c2 = _shift_pass(t, nbits, mask)
    t, c3 = _shift_pass(t, nbits, mask)
    out, g_top = _kogge(t, nbits, mask, width)
    return out, c1 + c2 + c3 + g_top


def _conv_into(acc, a, b_row, n: int, out_cols: int):
    """acc[:, i+j] += a[:, i] * b_row[j] — unrolled over i; each partial
    product is statically padded into place (pure adds, no scatters —
    scatters would leave VMEM/registers)."""
    rows = a.shape[0]
    for i in range(n):
        width = min(n, out_cols - i)
        if width <= 0:
            break
        contrib = a[:, i : i + 1] * b_row[:, :width]
        parts = []
        if i:
            parts.append(jnp.zeros((rows, i), jnp.uint32))
        parts.append(contrib)
        if out_cols - i - width:
            parts.append(jnp.zeros((rows, out_cols - i - width), jnp.uint32))
        acc = acc + (
            parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        )
    return acc


def _flag01(carry):
    """Collapse a small (<8) carry count to a 0/1 u32 flag — arithmetic
    select helper (no i1 vectors, no unsigned-min: both mis-lower in
    Mosaic)."""
    return (carry | (carry >> 1) | (carry >> 2)) & jnp.uint32(1)


@dataclass(frozen=True)
class _K:
    """Per-kernel constant bundle (everything the VMEM helpers need)."""

    n: int
    nbits: int
    mask: jnp.ndarray
    ninv: jnp.ndarray  # (1, n)
    p_row: jnp.ndarray  # (1, n)
    rm2n: jnp.ndarray  # (1, 2n): R - p in the high half
    rm_n: jnp.ndarray  # (1, n): R - p (R = 2^(nbits*n))
    one0: jnp.ndarray  # (1, n): one-hot limb 0


def _unpack_consts(ctx: ModCtx, consts_ref) -> _K:
    """consts_ref rows: 0 = ninv, 1 = p; 2..3 = R - p shifted into the
    high half (2n cols packed as two n-col rows; row 3 alone is the
    n-col R - p); 4 = one-hot limb 0."""
    return _K(
        n=ctx.n_limbs,
        nbits=ctx.limb_bits,
        mask=jnp.uint32((1 << ctx.limb_bits) - 1),
        ninv=consts_ref[0:1, :],
        p_row=consts_ref[1:2, :],
        rm2n=jnp.concatenate([consts_ref[2:3, :], consts_ref[3:4, :]], axis=1),
        rm_n=consts_ref[3:4, :],
        one0=consts_ref[4:5, :],
    )


def _conv_const_mxu(a, T0, T1):
    """conv(a, c) with the constant given as 6-bit Toeplitz pieces: the
    shared four-int8-matmul recombination (ops/limb_mxu.conv_const_mxu),
    here fed VMEM ref loads so the systolic array does the constant
    convolutions while the band intermediates never touch HBM."""
    from charon_tpu.ops.limb_mxu import conv_const_mxu

    return conv_const_mxu(a, T0, T1)


def _mont_core_mxu(k: _K, a, b, nT0, nT1, pT0, pT1):
    """_mont_core with the two constant-operand convolutions (t * ninv
    mod R and m * p) on the MXU. The data-dependent a * b product keeps
    the VPU unrolled conv — no constant matrix to feed the MXU with.
    Value ranges match the VPU path: every recombined column < 2^30
    (32 terms x 63^2 per 6-bit partial), inside what _normalize's three
    shift passes + Kogge resolve are built for."""
    rows = a.shape[0]
    n, nbits, mask = k.n, k.nbits, k.mask

    t = jnp.zeros((rows, 2 * n), jnp.uint32)
    t = _conv_into(t, a, b, n, 2 * n)
    t, _ = _normalize(t, nbits, mask, 2 * n)

    m = _conv_const_mxu(t[:, :n], nT0, nT1)
    m, _ = _normalize(m, nbits, mask, n)  # mod R: top carry dropped

    s = t + _conv_const_mxu(m, pT0, pT1)
    s2 = s + k.rm2n
    out1, _ = _normalize(s, nbits, mask, 2 * n)
    out2, carry2 = _normalize(s2, nbits, mask, 2 * n)
    flag = _flag01(carry2)
    hi1 = out1[:, n:]
    hi2 = out2[:, n:]
    return hi1 + (hi2 - hi1) * flag


def _mont_core(k: _K, a, b):
    """Full Montgomery multiply in VMEM: canonical n-limb result
    (mirrors limb.mont_mul's separated-operand algorithm step for step)."""
    rows = a.shape[0]
    n, nbits, mask = k.n, k.nbits, k.mask

    # 1. t = a * b over 2n columns
    t = jnp.zeros((rows, 2 * n), jnp.uint32)
    t = _conv_into(t, a, b, n, 2 * n)
    t, _ = _normalize(t, nbits, mask, 2 * n)

    # 2. m = (t mod R) * (-p^-1 mod R) mod R
    m = jnp.zeros((rows, n), jnp.uint32)
    m = _conv_into(m, t[:, :n], jnp.broadcast_to(k.ninv, (rows, n)), n, n)
    m, _ = _normalize(m, nbits, mask, n)

    # 3. s = t + m * p; final normalize fused with the conditional
    # subtract: lane2 adds (R - p) into the high columns, carry-out of
    # lane2 says hi >= p
    s = _conv_into(t, m, jnp.broadcast_to(k.p_row, (rows, n)), n, 2 * n)
    s2 = s + k.rm2n

    out1, _ = _normalize(s, nbits, mask, 2 * n)
    out2, carry2 = _normalize(s2, nbits, mask, 2 * n)
    flag = _flag01(carry2)
    hi1 = out1[:, n:]
    hi2 = out2[:, n:]
    return hi1 + (hi2 - hi1) * flag


def _mod_add(k: _K, x, y):
    """x + y mod p in VMEM (canonical inputs): raw lane + (R - p)
    adjustment lane, select on the adjusted lane's carry-out — the same
    trick as limb.addsub_mod_many."""
    s = x + y
    out1, _ = _normalize(s, k.nbits, k.mask, k.n)
    out2, c2 = _normalize(s + k.rm_n, k.nbits, k.mask, k.n)
    flag = _flag01(c2)
    return out1 + (out2 - out1) * flag


def _mod_sub(k: _K, x, y):
    """x - y mod p in VMEM: z = x + (R - 1 - y) + 1; carry-out of z says
    x >= y (take z), else take z + p."""
    z = x + (k.mask - y) + k.one0
    out1, c1 = _normalize(z, k.nbits, k.mask, k.n)
    out2, _ = _normalize(z + k.p_row, k.nbits, k.mask, k.n)
    flag = _flag01(c1)
    return out2 + (out1 - out2) * flag


def _fp2_mul_math(k: _K, mont, a0, a1, b0, b1):
    """Karatsuba Fp2 multiply on VMEM values: c0 = a0 b0 - a1 b1,
    c1 = (a0+a1)(b0+b1) - a0 b0 - a1 b1. `mont` is the Montgomery core
    (VPU or MXU-assisted)."""
    ta = _mod_add(k, a0, a1)
    tb = _mod_add(k, b0, b1)
    v0 = mont(a0, b0)
    v1 = mont(a1, b1)
    s = mont(ta, tb)
    return _mod_sub(k, v0, v1), _mod_sub(k, s, _mod_add(k, v0, v1))


def _fp2_sqr_math(k: _K, mont, a0, a1):
    """Fused Fp2 square: c0 = (a0+a1)(a0-a1), c1 = 2 a0 a1."""
    ta = _mod_add(k, a0, a1)
    ts = _mod_sub(k, a0, a1)
    c0 = mont(ta, ts)
    w = mont(a0, a1)
    return c0, _mod_add(k, w, w)


def _mont_kernel_body(ctx: ModCtx, a_ref, b_ref, consts_ref, out_ref):
    k = _unpack_consts(ctx, consts_ref)
    out_ref[:] = _mont_core(k, a_ref[:], b_ref[:])


def _mont_mxu_kernel_body(
    ctx: ModCtx, a_ref, b_ref, nT0, nT1, pT0, pT1, consts_ref, out_ref
):
    k = _unpack_consts(ctx, consts_ref)
    out_ref[:] = _mont_core_mxu(
        k, a_ref[:], b_ref[:], nT0[:], nT1[:], pT0[:], pT1[:]
    )


def _fp2_mul_kernel_body(
    ctx: ModCtx, a0_ref, a1_ref, b0_ref, b1_ref, consts_ref, c0_ref, c1_ref
):
    """Whole Karatsuba Fp2 multiply fused in VMEM: the prep sums, three
    Montgomery multiplies, and the recombination never touch HBM.

    This is the Miller loop's dominant op (~90% of pairing field work);
    the unfused path round-trips HBM between every stacked normalize and
    mont_mul (PERF.md 'Where the remaining gap is')."""
    k = _unpack_consts(ctx, consts_ref)
    mont = functools.partial(_mont_core, k)
    c0_ref[:], c1_ref[:] = _fp2_mul_math(
        k, mont, a0_ref[:], a1_ref[:], b0_ref[:], b1_ref[:]
    )


def _fp2_mul_mxu_kernel_body(
    ctx: ModCtx,
    a0_ref,
    a1_ref,
    b0_ref,
    b1_ref,
    nT0,
    nT1,
    pT0,
    pT1,
    consts_ref,
    c0_ref,
    c1_ref,
):
    """Fused Fp2 multiply with the constant convolutions of all three
    inner Montgomery multiplies on the MXU — the int8 pieces never leave
    VMEM (PERF.md int8-MXU lever, fold-into-Pallas step)."""
    k = _unpack_consts(ctx, consts_ref)
    mont = lambda x, y: _mont_core_mxu(  # noqa: E731
        k, x, y, nT0[:], nT1[:], pT0[:], pT1[:]
    )
    c0_ref[:], c1_ref[:] = _fp2_mul_math(
        k, mont, a0_ref[:], a1_ref[:], b0_ref[:], b1_ref[:]
    )


def _fp2_sqr_kernel_body(
    ctx: ModCtx, a0_ref, a1_ref, consts_ref, c0_ref, c1_ref
):
    """Fused Fp2 square — two Montgomery multiplies, all in VMEM."""
    k = _unpack_consts(ctx, consts_ref)
    mont = functools.partial(_mont_core, k)
    c0_ref[:], c1_ref[:] = _fp2_sqr_math(k, mont, a0_ref[:], a1_ref[:])


def _fp2_sqr_mxu_kernel_body(
    ctx: ModCtx, a0_ref, a1_ref, nT0, nT1, pT0, pT1, consts_ref, c0_ref, c1_ref
):
    k = _unpack_consts(ctx, consts_ref)
    mont = lambda x, y: _mont_core_mxu(  # noqa: E731
        k, x, y, nT0[:], nT1[:], pT0[:], pT1[:]
    )
    c0_ref[:], c1_ref[:] = _fp2_sqr_math(k, mont, a0_ref[:], a1_ref[:])


@functools.lru_cache(maxsize=None)
def _ctx_consts(ctx: ModCtx) -> np.ndarray:
    """(5, n) constant rows: ninv, p, (R-p) low half, (R-p) high half,
    one-hot limb 0 — rows 2..3 concatenate to the 2n-col adjustment lane
    (row 3 alone is the n-col R - p used by the mod-add helper)."""
    n = ctx.n_limbs
    out = np.zeros((5, n), np.uint32)
    out[0] = np.asarray(ctx.ninv, np.uint32)
    out[1] = np.asarray(ctx.limbs, np.uint32)
    rm2n = np.zeros(2 * n, np.uint32)
    rm2n[n:] = np.asarray(_r_minus_m(ctx), np.uint32)
    out[2] = rm2n[:n]
    out[3] = rm2n[n:]
    out[4, 0] = 1
    return out


@functools.lru_cache(maxsize=None)
def _toeplitz_consts(ctx: ModCtx):
    """int8 Toeplitz piece matrices for the two constant convolutions
    (shared geometry with ops/limb_mxu.py): (nT0, nT1) [n, n] for
    -m^-1 mod R, (pT0, pT1) [n, 2n] for the modulus."""
    from charon_tpu.ops.limb_mxu import _modulus_toeplitz, _ninv_toeplitz

    nT0, nT1 = _ninv_toeplitz(ctx)
    pT0, pT1 = _modulus_toeplitz(ctx)
    return nT0, nT1, pT0, pT1


def _mxu_usable(ctx: ModCtx) -> bool:
    return ctx.limb_bits == 12 and ctx.np_dtype is np.uint32


def _vma_of(*arrays) -> frozenset:
    """Union of the operands' shard_map varying axes: a pallas_call
    under `jax.shard_map(check_vma=True)` must declare them on its
    out_shape (empty outside shard_map, where it is ignored)."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


@functools.lru_cache(maxsize=None)
def _mont_call(
    ctx: ModCtx,
    interpret: bool,
    mxu: bool = False,
    vma: frozenset = frozenset(),
):
    """Gridless pallas_call over one (TILE, n_limbs) block. Batches
    larger than TILE run it under lax.map: a device-side map over a
    fixed-shape kernel compiles the kernel exactly once. (The gridless
    design dates from an installation whose Mosaic could not legalize
    block index maps; a grid has not been tried on the installed one.)"""
    n = ctx.n_limbs
    body = _mont_mxu_kernel_body if mxu else _mont_kernel_body
    n_in = 7 if mxu else 3
    kernel = functools.partial(body, ctx)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((TILE, n), jnp.uint32, vma=vma),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n_in,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _fp2_call(
    ctx: ModCtx,
    kind: str,
    interpret: bool,
    mxu: bool = False,
    vma: frozenset = frozenset(),
):
    """Gridless pallas_call for the fused Fp2 kernels (same lax.map
    chunking strategy as the mont kernel)."""
    n = ctx.n_limbs
    out_shape = (
        jax.ShapeDtypeStruct((TILE, n), jnp.uint32, vma=vma),
        jax.ShapeDtypeStruct((TILE, n), jnp.uint32, vma=vma),
    )
    if kind == "mul":
        body = _fp2_mul_mxu_kernel_body if mxu else _fp2_mul_kernel_body
        n_in = 5 + (4 if mxu else 0)
    else:
        body = _fp2_sqr_mxu_kernel_body if mxu else _fp2_sqr_kernel_body
        n_in = 3 + (4 if mxu else 0)
    return pl.pallas_call(
        functools.partial(body, ctx),
        out_shape=out_shape,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n_in,
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )


def _resolve_mxu(ctx: ModCtx, mxu: bool | None) -> bool:
    """None = follow limb's MXU dispatch mode (limb.set_mxu, owned at
    startup by core/autotune.KernelConfig); True/False = forced for
    this call."""
    if mxu is None:
        from charon_tpu.ops import limb as _limb

        mxu = _limb._mxu_active(ctx)
    return bool(mxu) and _mxu_usable(ctx)


def _mxu_extras(ctx: ModCtx, mxu: bool) -> tuple:
    if not mxu:
        return ()
    return tuple(jnp.asarray(T) for T in _toeplitz_consts(ctx))


def _run_fp2(
    ctx: ModCtx, kind: str, operands, interpret: bool, mxu: bool | None
):
    """Flatten/pad a list of (..., n) operand arrays to TILE-row chunks
    and run the fused kernel; returns the two (..., n) outputs."""
    if ctx.np_dtype is not np.uint32:
        raise ValueError("pallas fp2 kernels require the uint32 limb geometry")
    mxu = _resolve_mxu(ctx, mxu)
    operands = jnp.broadcast_arrays(*operands)
    batch_shape = operands[0].shape[:-1]
    n = ctx.n_limbs
    flats = [o.reshape(-1, n) for o in operands]
    rows = flats[0].shape[0]
    padded = -(-rows // TILE) * TILE
    if padded != rows:
        flats = [jnp.pad(f, ((0, padded - rows), (0, 0))) for f in flats]
    extras = _mxu_extras(ctx, mxu)
    consts = jnp.asarray(_ctx_consts(ctx))
    call = _fp2_call(ctx, kind, interpret, mxu, _vma_of(*flats))
    if padded == TILE:
        c0, c1 = call(*flats, *extras, consts)
    else:
        chunks = padded // TILE
        c0, c1 = jax.lax.map(
            lambda xs: call(*xs, *extras, consts),
            tuple(f.reshape(chunks, TILE, n) for f in flats),
        )
        c0 = c0.reshape(padded, n)
        c1 = c1.reshape(padded, n)
    return (
        c0[:rows].reshape(*batch_shape, n),
        c1[:rows].reshape(*batch_shape, n),
    )


def fp2_mul_pallas(
    ctx: ModCtx, a, b, interpret: bool = False, mxu: bool | None = None
):
    """Fused Fp2 Karatsuba multiply: a, b are (c0, c1) tuples of reduced
    Montgomery limb arrays; returns the product tuple. Drop-in for
    ops/fptower.fp2_mul on the uint32 geometry."""
    return _run_fp2(ctx, "mul", (a[0], a[1], b[0], b[1]), interpret, mxu)


def fp2_sqr_pallas(
    ctx: ModCtx, a, interpret: bool = False, mxu: bool | None = None
):
    """Fused Fp2 square; drop-in for ops/fptower.fp2_sqr."""
    return _run_fp2(ctx, "sqr", (a[0], a[1]), interpret, mxu)


def mont_mul_pallas(
    ctx: ModCtx, a, b, interpret: bool = False, mxu: bool | None = None
):
    """Drop-in for limb.mont_mul on the uint32 geometry: reduced
    Montgomery-form inputs with arbitrary broadcastable batch dims."""
    if ctx.np_dtype is not np.uint32:
        raise ValueError("pallas mont_mul requires the uint32 limb geometry")
    mxu = _resolve_mxu(ctx, mxu)
    a, b = jnp.broadcast_arrays(a, b)
    batch_shape = a.shape[:-1]
    n = ctx.n_limbs
    flat_a = a.reshape(-1, n)
    flat_b = b.reshape(-1, n)
    rows = flat_a.shape[0]
    padded = -(-rows // TILE) * TILE
    if padded != rows:
        pad = ((0, padded - rows), (0, 0))
        flat_a = jnp.pad(flat_a, pad)
        flat_b = jnp.pad(flat_b, pad)
    extras = _mxu_extras(ctx, mxu)
    consts = jnp.asarray(_ctx_consts(ctx))
    call = _mont_call(ctx, interpret, mxu, _vma_of(flat_a, flat_b))
    if padded == TILE:
        out = call(flat_a, flat_b, *extras, consts)
    else:
        chunks = padded // TILE
        out = jax.lax.map(
            lambda ab: call(ab[0], ab[1], *extras, consts),
            (
                flat_a.reshape(chunks, TILE, n),
                flat_b.reshape(chunks, TILE, n),
            ),
        ).reshape(padded, n)
    return out[:rows].reshape(*batch_shape, n)
