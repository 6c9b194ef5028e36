"""The modular add / sub families of `ops/limb.py` are traced once a shape
(PR 39: inlined jits, `limb._traced_once`): most of a pairing program's
trace, and four programs traced one after the other were what kept the
two-kind cell's set-up out of the harness's budget. A plane program's
compile-cache key is its lowered module, so the jits may shorten the trace
and change nothing else."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from charon_tpu.crypto.fields import P
from charon_tpu.ops import fptower, limb


def _as_it_was(wrapped, *consts):
    """A family as it was before `limb._traced_once`: the plain function,
    its limb constants made one array a call."""
    fn = wrapped.__wrapped__

    def call(ctx, *pairs):
        if not any(pairs):
            return fn(ctx, *pairs, *(None for _ in consts))
        return fn(ctx, *pairs, *(jnp.asarray(c(ctx)) for c in consts))

    return call


@pytest.mark.parametrize("ctx", [limb.FP, limb.FP32], ids=["u64-limbs", "u32-limbs"])
def test_tracing_a_shape_once_lowers_the_module_the_plain_functions_gave(ctx, monkeypatch):
    """Not an op, not a constant, not their order differs (the outer trace
    hoists one constant an array object, which is why the limb constants
    are made outside the jits, one array a call)."""

    def graph(a, b):
        # all three families, the same shapes again and again, and one more shape
        m = fptower.fp12_mul(ctx, fptower.fp12_sqr(ctx, a), b)
        return fptower.fp12_mul(ctx, m, a), fptower.fp2_mul_xi(ctx, a[0][0])

    fp2 = (np.zeros((3, ctx.n_limbs), ctx.np_dtype),) * 2
    fp12 = ((fp2,) * 3,) * 2
    traced_once = jax.jit(graph).lower(fp12, fp12).as_text()
    jax.clear_caches()
    monkeypatch.setattr(limb, "_add_many", _as_it_was(limb._add_many, limb._r_minus_m))
    monkeypatch.setattr(limb, "_sub_many", _as_it_was(limb._sub_many, limb._one0, limb._modulus))
    monkeypatch.setattr(limb, "addsub_mod_many", _as_it_was(
        limb.addsub_mod_many, limb._r_minus_m, limb._one0, limb._modulus))
    assert jax.jit(graph).lower(fp12, fp12).as_text() == traced_once
    assert traced_once.count("stablehlo.constant") > 10


def test_a_shape_met_again_is_not_traced_again(monkeypatch):
    traces = []
    plain = limb._add_many.__wrapped__

    def counted(ctx, pairs, rm):
        traces.append(len(pairs))
        return plain(ctx, pairs, rm)

    monkeypatch.setattr(limb, "_add_many", limb._traced_once(counted, limb._r_minus_m))

    def graph(a, b):
        for _ in range(5):
            a = limb.add_mod(limb.FP, a, b)
        return limb.add_mod_many(limb.FP, [(a, b), (b, a)])

    x = np.zeros((4, limb.FP.n_limbs), limb.FP.np_dtype)
    jaxpr = jax.make_jaxpr(graph)(x, x)
    assert traces == [1, 2]  # one trace a shape: five adds of one pair, one of two
    assert "pjit" not in str(jaxpr)  # and the equations are the outer trace's own


def test_concrete_limbs_are_added_op_by_op_as_ever(monkeypatch):
    """Outside a trace the families run as they always did: a jit of their
    own a shape would compile where nothing is being traced."""
    monkeypatch.setattr(jax, "jit", None)  # nobody asks for one from here on
    a = jnp.asarray(limb.ctx_pack(limb.FP, [3, P - 1]))
    assert limb.ctx_unpack(limb.FP, limb.add_mod(limb.FP, a, a)) == [6, P - 2]
    assert limb.ctx_unpack(limb.FP, limb.sub_mod(limb.FP, a, a)) == [0, 0]
    assert limb.add_mod_many(limb.FP, []) == []
    assert limb.addsub_mod_many(limb.FP, [], []) == ([], [])
