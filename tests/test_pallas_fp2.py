"""Fused Pallas Fp2 kernels vs the stacked-XLA tower
(ops/pallas_mont.py fp2_mul_pallas / fp2_sqr_pallas; interpret mode on
CPU — the same kernels run compiled on the TPU). The fusion keeps the
Karatsuba prep, three Montgomery multiplies, and recombination in VMEM
(the XLA path is HBM-bound between those steps, PERF.md).

ALL cases run in ONE fresh subprocess: this file's fresh interpret-mode
compiles land ~50 tests into the slow tier, where this image's jaxlib
segfaults — in the cache write with writes enabled, and inside
backend_compile_and_load itself with writes disabled (both reproduced
2026-07-31/08-01; CI.md "Known environment flake"). A fresh process
with few programs compiles the same kernels safely and caches them."""

from __future__ import annotations

import pytest

# Compile-heavy crypto tier: run with `pytest -m slow` (see CI.md).
pytestmark = pytest.mark.slow

_FP2_SCRIPT = """
import random
from unittest import mock

import numpy as np
import jax.numpy as jnp

from charon_tpu.ops import fptower as T
from charon_tpu.ops import limb
from charon_tpu.ops import pallas_mont as PK

CTX = limb.FP32
limb.set_pallas(False)  # reference values come from the pure-XLA tower


def pack(vals):
    return jnp.asarray(limb.pack_mont_host(CTX, vals))


def rand_fp2(rng, n):
    return (
        pack([rng.randrange(CTX.modulus) for _ in range(n)]),
        pack([rng.randrange(CTX.modulus) for _ in range(n)]),
    )


def assert_fp2_equal(got, want, label):
    for i in range(2):
        assert np.array_equal(np.asarray(got[i]), np.asarray(want[i])), (
            label + " c%d mismatch" % i
        )


# mul/sqr match the XLA tower
rng = random.Random(23)
a, b = rand_fp2(rng, 8), rand_fp2(rng, 8)
assert_fp2_equal(
    PK.fp2_mul_pallas(CTX, a, b, interpret=True), T.fp2_mul(CTX, a, b), "mul"
)
assert_fp2_equal(
    PK.fp2_sqr_pallas(CTX, a, interpret=True), T.fp2_sqr(CTX, a), "sqr"
)

# edge values
edge = [0, 1, CTX.modulus - 1, CTX.modulus // 2, 2, CTX.modulus - 2, 0, 1]
ae = (pack(edge), pack(list(reversed(edge))))
be = (pack(list(reversed(edge))), pack(edge))
assert_fp2_equal(
    PK.fp2_mul_pallas(CTX, ae, be, interpret=True),
    T.fp2_mul(CTX, ae, be),
    "mul-edge",
)
assert_fp2_equal(
    PK.fp2_sqr_pallas(CTX, ae, interpret=True), T.fp2_sqr(CTX, ae), "sqr-edge"
)

# rows > TILE exercise the lax.map chunking + pad/unpad reshape
rng = random.Random(29)
n = PK.TILE + 40
am, bm = rand_fp2(rng, n), rand_fp2(rng, n)
assert_fp2_equal(
    PK.fp2_mul_pallas(CTX, am, bm, interpret=True),
    T.fp2_mul(CTX, am, bm),
    "mul-multitile",
)

# set_fp2_fusion routes fp2_batch between the fused-kernel route and the
# stacked-XLA route while pallas stays active (bench.py's middle rung)
rng = random.Random(37)
af, bf = rand_fp2(rng, 4), rand_fp2(rng, 4)
sentinel = [("fused", "fused")]
probes = {"n": 0}


def first_probe_active(ctx):
    probes["n"] += 1
    return probes["n"] == 1


with mock.patch.object(limb, "_pallas_active", first_probe_active):
    with mock.patch.object(
        T, "_fp2_batch_pallas", return_value=sentinel
    ) as fused:
        assert T.fp2_batch(CTX, [("mul", af, bf)]) == sentinel
        assert fused.called

try:
    T.set_fp2_fusion(False)
    with mock.patch.object(
        T, "_fp2_batch_pallas", side_effect=AssertionError("fused")
    ):
        (got,) = T.fp2_batch(CTX, [("mul", af, bf)])
finally:
    T.set_fp2_fusion(True)
want = T.fp2_mul(CTX, af, bf)  # pallas fully off here
for i in range(2):
    assert np.array_equal(np.asarray(got[i]), np.asarray(want[i]))

# fp2_batch pallas route (stacked mul/sqr/mul_fp) matches XLA op for op
rng = random.Random(31)
ad, bd, cd = (rand_fp2(rng, 6) for _ in range(3))
s = pack([rng.randrange(CTX.modulus) for _ in range(6)])
ops = [
    ("mul", ad, bd),
    ("sqr", cd),
    ("mul_fp", bd, s),
    ("mul", cd, ad),
    ("sqr", ad),
]
want_ops = T.fp2_batch(CTX, ops)  # pallas disabled above
orig_call = PK._fp2_call
with mock.patch.object(
    PK,
    "_fp2_call",
    lambda ctx, kind, interpret, mxu=False, vma=frozenset(): orig_call(
        ctx, kind, True, mxu, vma
    ),
):
    got_ops = T._fp2_batch_pallas(CTX, ops)
assert len(got_ops) == len(want_ops)
for i, (g, w) in enumerate(zip(got_ops, want_ops)):
    assert_fp2_equal(g, w, "op%d" % i)

# MXU-fused variants (Toeplitz int8 matmuls inside the fused multiply)
# are bit-identical to the XLA tower and the VPU kernels
rng = random.Random(29)
ax, bx = rand_fp2(rng, 8), rand_fp2(rng, 8)
assert_fp2_equal(
    PK.fp2_mul_pallas(CTX, ax, bx, interpret=True, mxu=True),
    T.fp2_mul(CTX, ax, bx),
    "mul-mxu",
)
assert_fp2_equal(
    PK.fp2_sqr_pallas(CTX, ax, interpret=True, mxu=True),
    T.fp2_sqr(CTX, ax),
    "sqr-mxu",
)
assert_fp2_equal(
    PK.fp2_mul_pallas(CTX, ax, bx, interpret=True, mxu=True),
    PK.fp2_mul_pallas(CTX, ax, bx, interpret=True, mxu=False),
    "mul-mxu-vs-vpu",
)
print("FP2-PALLAS-OK")
"""


def test_fp2_pallas_full_suite():
    """Fused-Fp2 kernel suite: mul/sqr vs XLA, edge values, multi-tile
    chunking, fusion-flag routing, fp2_batch dispatch parity, and the
    MXU variants — one compile set, one fresh subprocess (see module
    docstring)."""
    from isolation_util import ISOLATED_HEADER, run_isolated

    run_isolated(ISOLATED_HEADER + _FP2_SCRIPT, "FP2-PALLAS-OK", timeout=3000)
