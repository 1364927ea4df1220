"""Differential tests: batched limb arithmetic vs. Python big ints.

Covers both consensus moduli (bn256 base/scalar fields, secp256k1 base/
scalar fields) — the same ModArith machinery backs the pairing kernel and
the ECDSA kernel, mirroring how the reference's gfP asm and libsecp256k1
field code each serve one curve (SURVEY.md §2.3).
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from gethsharding_tpu.crypto import bn256 as bn_ref
from gethsharding_tpu.crypto import secp256k1 as secp_ref
from gethsharding_tpu.ops import limb

MODULI = {
    "bn256_p": bn_ref.P,
    "bn256_n": bn_ref.N,
    "secp_p": secp_ref.P,
    "secp_n": secp_ref.N,
}


def rand_lazy(rng, n):
    """Random *lazy* elements: any value in [0, 2^264)."""
    return [rng.randrange(limb.RADIX) for _ in range(n)]


@pytest.fixture(scope="module")
def rng():
    return random.Random(0xC0FFEE)


@pytest.mark.parametrize("name", sorted(MODULI))
def test_roundtrip_and_canon(name, rng):
    p = MODULI[name]
    fp = limb.ModArith(p)
    vals = rand_lazy(rng, 8) + [0, 1, p - 1, p, p + 1, limb.RADIX - 1]
    x = jnp.asarray(limb.ints_to_limbs(vals))
    got = fp.to_ints(x)
    for v, g in zip(vals, got):
        assert int(g) == v % p


@pytest.mark.parametrize("name", sorted(MODULI))
def test_add_sub_mul_batch(name, rng):
    p = MODULI[name]
    fp = limb.ModArith(p)
    n = 16
    xs, ys = rand_lazy(rng, n), rand_lazy(rng, n)
    # adversarial corners: max lazy values, zero, p-1 pairs
    xs[:3] = [limb.RADIX - 1, 0, p - 1]
    ys[:3] = [limb.RADIX - 1, limb.RADIX - 1, p - 1]
    x = jnp.asarray(limb.ints_to_limbs(xs))
    y = jnp.asarray(limb.ints_to_limbs(ys))

    for op, ref in [
        (fp.add, lambda a, b: (a + b) % p),
        (fp.sub, lambda a, b: (a - b) % p),
        (fp.mul, lambda a, b: (a * b) % p),
    ]:
        out = fp.to_ints(op(x, y))
        for a, b, g in zip(xs, ys, out):
            assert int(g) == ref(a, b), op.__name__

    # chained ops stay lazily-correct: (x*y + x - y)^2
    z = fp.sqr(fp.sub(fp.add(fp.mul(x, y), x), y))
    out = fp.to_ints(z)
    for a, b, g in zip(xs, ys, out):
        assert int(g) == pow(a * b + a - b, 2, p)


@pytest.mark.parametrize("name", ["bn256_p", "secp_p"])
def test_neg_small_pow_inv(name, rng):
    p = MODULI[name]
    fp = limb.ModArith(p)
    xs = rand_lazy(rng, 4) + [0, 1]
    x = jnp.asarray(limb.ints_to_limbs(xs))

    neg = fp.to_ints(fp.neg(x))
    for a, g in zip(xs, neg):
        assert int(g) == (-a) % p

    sm = fp.to_ints(fp.mul_small(x, 9))
    for a, g in zip(xs, sm):
        assert int(g) == (9 * a) % p

    e = 0x1234567890ABCDEF
    pw = fp.to_ints(fp.pow_static(x, e))
    for a, g in zip(xs, pw):
        assert int(g) == pow(a, e, p)

    inv = fp.to_ints(fp.inv(x))
    for a, g in zip(xs, inv):
        assert int(g) == (pow(a % p, p - 2, p) if a % p else 0)


def test_predicates_and_select():
    p = MODULI["bn256_p"]
    fp = limb.ModArith(p)
    vals = [0, p, 1, p + 1, 2 * p]
    x = jnp.asarray(limb.ints_to_limbs(vals))
    assert list(np.asarray(fp.is_zero(x))) == [True, True, False, False, True]

    y = jnp.asarray(limb.ints_to_limbs([p, 0, p + 1, 1, 5]))
    assert list(np.asarray(fp.eq(x, y))) == [True, True, True, True, False]

    cond = jnp.asarray([True, False, True, False, True])
    sel = fp.to_ints(fp.select(cond, x, y))
    assert [int(v) for v in sel] == [0, 0, 1, 1, 0]


def test_batch_shapes_nd():
    """Ops must be batch-first over arbitrary leading axes (vmap-free)."""
    p = MODULI["bn256_p"]
    fp = limb.ModArith(p)
    rng = random.Random(7)
    vals = [[rng.randrange(p) for _ in range(3)] for _ in range(2)]
    x = jnp.asarray(np.stack([limb.ints_to_limbs(row) for row in vals]))
    out = fp.to_ints(fp.mul(x, x))
    assert out.shape == (2, 3)
    for i in range(2):
        for j in range(3):
            assert int(out[i][j]) == pow(vals[i][j], 2, p)


def test_assoc_carry_impl_matches_scan(monkeypatch):
    """All carry implementations (scan / assoc / unroll) must agree
    exactly; the non-default paths are env-selected and would otherwise
    go untested."""
    p = MODULI["bn256_p"]
    rng = random.Random(11)
    vals_a = [rng.randrange(p) for _ in range(8)]
    vals_b = [rng.randrange(p) for _ in range(8)]
    x = jnp.asarray(limb.ints_to_limbs(vals_a))
    y = jnp.asarray(limb.ints_to_limbs(vals_b))

    fp = limb.ModArith(p)
    expect = [a * b % p for a, b in zip(vals_a, vals_b)]
    got_scan = fp.to_ints(fp.mul(x, y))
    monkeypatch.setattr(limb, "CARRY_IMPL", "assoc")
    got_assoc = fp.to_ints(fp.sub(fp.mul(x, y), y))
    monkeypatch.setattr(limb, "CARRY_IMPL", "unroll")
    got_unroll = fp.to_ints(fp.sub(fp.mul(x, y), y))
    monkeypatch.setattr(limb, "CARRY_IMPL", "scan")
    assert [int(v) for v in got_scan] == expect
    expect_sub = [(a * b - b) % p for a, b in zip(vals_a, vals_b)]
    assert [int(v) for v in got_assoc] == expect_sub
    assert [int(v) for v in got_unroll] == expect_sub


_CONV_SMALL = [(22, 22), (25, 49), (3, 7), (1, 5), (22, 43)]


def _shape_id(shape):
    return "x".join(map(str, shape))


@pytest.mark.parametrize("shape", _CONV_SMALL, ids=_shape_id)
@pytest.mark.parametrize("impl", ["shift", "slices", "gather", "mxu8"])
def test_conv_impls_agree(impl, shape):
    """Every conv_cols implementation computes the same anti-diagonal
    sums (the autotune sweep may deploy any of them)."""
    L, M = shape
    rng = np.random.default_rng(7 + 100 * L + M)
    prod = rng.integers(-2**20, 2**20, size=(2, 3, L, M),
                        dtype=np.int64).astype(np.int32)
    if impl == "mxu8":
        # mxu8's int8-plane split assumes non-negative entries (the
        # limb-product contract: products of canonical <2^12 limbs)
        prod = np.abs(prod)
    want = limb.conv_cols(jnp.asarray(prod), impl="onehot")
    got = limb.conv_cols(jnp.asarray(prod), impl=impl)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_relaxed_norm_matches_exact(monkeypatch):
    """GETHSHARDING_TPU_NORM=relaxed (wide form): same residues as the
    exact ripple on mul/add/sub chains, and the quasi-canonical limb
    contract holds (limbs in [-1, 2^12 + 64]) — the range every fused
    accumulator's int32 proof budgets for."""
    if limb.LIMB_FORM != "wide":
        pytest.skip("relaxed normalize is wide-form only")
    if limb.CONV_IMPL == "mxu8":
        pytest.skip("mxu8 conv requires non-negative products; "
                    "incompatible with relaxed limbs")
    for name in ("bn256_p", "secp_p", "secp_n", "bn256_n"):
        _relaxed_norm_case(monkeypatch, MODULI[name])


def _relaxed_norm_case(monkeypatch, p):
    fp = limb.ModArith(p)
    rng = random.Random(99)
    vals_a = [rng.randrange(p) for _ in range(16)]
    vals_b = [rng.randrange(p) for _ in range(16)]
    x = jnp.asarray(limb.ints_to_limbs(vals_a))
    y = jnp.asarray(limb.ints_to_limbs(vals_b))

    def chain():
        z = fp.mul(fp.sub(fp.mul(x, y), y), fp.sub(x, fp.mul(y, y)))
        return fp.sub(z, fp.mul(z, x))

    monkeypatch.setattr(limb, "NORM_IMPL", "relaxed")
    # sub-heavy chain: borrows exercise the negative-limb transients the
    # top-carry re-fuse exists for
    z = chain()
    got = [int(v) for v in fp.to_ints(z)]
    arr = np.asarray(z)
    assert arr.min() >= -1 and arr.max() <= (1 << limb.LIMB_BITS) + 64, (
        arr.min(), arr.max())
    monkeypatch.setattr(limb, "NORM_IMPL", "exact")
    want = [int(v) for v in fp.to_ints(chain())]
    expect = [(((a * b - b) % p) * ((a - b * b) % p) % p) for a, b
              in zip(vals_a, vals_b)]
    expect = [(e - e * a) % p for e, a in zip(expect, vals_a)]
    assert got == want == expect
