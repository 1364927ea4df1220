"""The final-exponentiation mega-kernel (ops/pallas_finalexp.py) vs the
XLA path, layer by layer:

1. helper differentials — the kernel's relaxed normalize / conv / xi /
   fp12-mul / frobenius as plain XLA ops, value-compared (mod p) against
   ops/bn256_jax + host scalar crypto;
2. program oracle — the full instruction stream executed with the same
   helpers as unrolled XLA (`run_program_xla`) must reproduce
   `pairing_is_one` bit-for-bit on real Miller products;
3. the Pallas kernel in interpreter mode must match the oracle.

All CPU (conftest forces virtual devices); on the chip `chip_smoke.py`'s
kernel leg runs the same checks compiled."""

import os

import numpy as np
import jax.numpy as jnp
import pytest

from gethsharding_tpu.crypto import bn256 as ref
from gethsharding_tpu.ops import bn256_jax as k
from gethsharding_tpu.ops import pallas_finalexp as m
from gethsharding_tpu.ops.limb import NLIMBS, int_to_limbs, limbs_to_int

def slow(fn):
    """Heavy differential: excluded from BOTH fast tiers (the `-m "not
    slow"` marker tier and the GETHSHARDING_SKIP_SLOW env tier); the
    module's cheap helper-parity tests stay fast in both."""
    fn = pytest.mark.skipif(
        os.environ.get("GETHSHARDING_SKIP_SLOW") == "1",
        reason="GETHSHARDING_SKIP_SLOW=1")(fn)
    return pytest.mark.slow(fn)


def _vals_mod_p(limbs_rows) -> np.ndarray:
    """(..., W, B) kernel-layout limbs -> (..., B) integers mod p."""
    arr = np.asarray(limbs_rows)
    out = np.zeros(arr.shape[:-2] + arr.shape[-1:], dtype=object)
    for i in range(arr.shape[-2]):
        out = out + (arr[..., i, :].astype(object) << (12 * i))
    return out % m.P


def _rand_quasi(rng, shape):
    """Quasi-canonical kernel-form limbs: values in [-1, 4160]."""
    return rng.integers(-1, 4161, shape + (m.KNL,)).astype(np.int32)


_C = None


def _consts():
    global _C
    if _C is None:
        _C = m.Consts(*(jnp.asarray(c) for c in m._NP_CONSTS))
    return _C


def _to_rows(x):
    """(..., W) -> (..., W, 1) single-lane kernel layout."""
    return jnp.asarray(np.asarray(x)[..., None])


def test_normalize_value_and_bounds():
    rng = np.random.default_rng(51)
    z = rng.integers(-(1 << 29), 1 << 29, (8, m.KNCOLS)).astype(np.int32)
    # make represented values non-negative: add the conv pad
    z = z + np.pad(m._PAD547, (0, m.KNCOLS - m._PAD547.shape[0]))
    out = np.asarray(m._normalize(_to_rows(z), _consts()))
    assert out.shape == (8, m.KNL, 1)
    assert out.min() >= -1 and out.max() <= (1 << 12) + 64
    want = _vals_mod_p(_to_rows(z))
    got = _vals_mod_p(out)
    assert (want == got).all()


def test_conv_matches_schoolbook():
    rng = np.random.default_rng(52)
    a = rng.integers(0, 1 << 12, (3, m.KNL)).astype(np.int32)
    b = rng.integers(0, 1 << 12, (3, m.KNL)).astype(np.int32)
    got = np.asarray(m._conv(_to_rows(a), _to_rows(b)))[..., 0]
    for i in range(3):
        va = limbs_to_int(a[i])
        vb = limbs_to_int(b[i])
        assert limbs_to_int(got[i].astype(object)) == va * vb


def test_conv_broadcast_dims_and_signed_limbs():
    """The columns are the plain schoolbook sums on quasi-canonical
    inputs (incl. the -1 limbs relaxed normalize can leave) and with
    broadcast leading dims — the shapes the fp12 paths actually use."""
    rng = np.random.default_rng(57)
    u = rng.integers(-1, (1 << 12) + 65, (2, 3, m.KNL, 4)).astype(np.int32)
    v = rng.integers(-1, (1 << 12) + 65, (3, m.KNL, 4)).astype(np.int32)
    want = np.zeros((2, 3, m.KNCOLS, 4), np.int64)
    for i in range(m.KNL):
        for j in range(m.KNL):
            want[..., i + j, :] += u[..., i, :].astype(np.int64) * v[..., j, :]
    got = np.asarray(m._conv(jnp.asarray(u), jnp.asarray(v)))
    assert got.shape == (2, 3, m.KNCOLS, 4)
    assert (got == want).all()


def test_mul_xi_value_parity():
    rng = np.random.default_rng(53)
    x = _rand_quasi(rng, (4, 6, 2))
    out = np.asarray(m._mul_xi(jnp.asarray(x[..., None]), _consts()))
    vals = _vals_mod_p(out)[..., 0]
    xv = _vals_mod_p(x[..., None])[..., 0]
    for idx in np.ndindex(4, 6):
        a, b = int(xv[idx + (0,)]), int(xv[idx + (1,)])
        assert int(vals[idx + (0,)]) == (9 * a - b) % m.P
        assert int(vals[idx + (1,)]) == (a + 9 * b) % m.P


def _host_fp12_from_vals(vals):
    """vals (6, 2) ints -> ref.Fp12 (w-basis -> tower), for the scalar
    oracle. w-coeff k (a + b i) contributes to c_{k%2} v^{k//2}."""
    c0 = [None] * 3
    c1 = [None] * 3
    for kk in range(6):
        t = ref.Fp2(int(vals[kk, 0]), int(vals[kk, 1]))
        if kk % 2 == 0:
            c0[kk // 2] = t
        else:
            c1[kk // 2] = t
    return ref.Fp12(ref.Fp6(*c0), ref.Fp6(*c1))


def _fp12_to_vals(f):
    """ref.Fp12 -> (6, 2) object ints in the w-basis."""
    out = np.zeros((6, 2), dtype=object)
    for kk in range(6):
        six = f.c0 if kk % 2 == 0 else f.c1
        c = (six.c0, six.c1, six.c2)[kk // 2]
        out[kk] = (c.a % m.P, c.b % m.P)
    return out


@slow  # ~5 s of eager host fp12 parity; conv/normalize/mul_xi stay as the fast guards
def test_fp12_mul_value_parity():
    rng = np.random.default_rng(54)
    x = _rand_quasi(rng, (3, 6, 2))
    y = _rand_quasi(rng, (3, 6, 2))
    out = np.asarray(m._fp12_mul(jnp.asarray(x[..., None]),
                                 jnp.asarray(y[..., None]), _consts()))
    assert out.min() >= -1 and out.max() <= (1 << 12) + 64
    got = _vals_mod_p(out)[..., 0]
    xv = _vals_mod_p(x[..., None])[..., 0]
    yv = _vals_mod_p(y[..., None])[..., 0]
    for i in range(3):
        want = _host_fp12_from_vals(xv[i]) * _host_fp12_from_vals(yv[i])
        wv = _fp12_to_vals(want)
        assert (got[i] == wv).all()


@slow  # ~10 s (three frobenius powers through the XLA oracle)
def test_frobenius_value_parity():
    """Oracle: bn256_jax.fp12_frobenius (itself pinned to the scalar
    reference in test_bn256_jax) on the same values in ambient limbs."""
    rng = np.random.default_rng(55)
    x = _rand_quasi(rng, (2, 6, 2))
    xv = _vals_mod_p(x[..., None])[..., 0]
    amb = np.zeros((2, 6, 2, NLIMBS), np.int32)
    for idx in np.ndindex(2, 6, 2):
        amb[idx] = int_to_limbs(int(xv[idx]), NLIMBS)
    for n in (1, 2, 3):
        out = np.asarray(m._frob(jnp.asarray(x[..., None]), jnp.int32(n), _consts()))
        got = _vals_mod_p(out)[..., 0]
        want = np.asarray(k.FP.canon(k.fp12_frobenius(jnp.asarray(amb), n)))
        for idx in np.ndindex(2, 6, 2):
            assert int(got[idx]) == limbs_to_int(want[idx]), (n, idx)


def _miller_products(n_good: int, n_bad: int):
    """Real pairing workloads: miller products whose final exp is one
    (valid BLS-style checks) and ones where it is not."""
    rng = np.random.default_rng(56)
    fs, wants = [], []
    for j in range(n_good + n_bad):
        a = int.from_bytes(rng.bytes(31), "big") % (ref.N - 3) + 2
        p1 = ref.g1_mul(a, ref.G1_GEN)
        q2 = ref.g2_mul(a, ref.G2_GEN)
        if j >= n_good:  # tamper: shift the G1 point
            p1 = ref.g1_add(p1, ref.G1_GEN)
        px, py, _ = k.g1_to_limbs([p1, ref.g1_neg(ref.G1_GEN)])
        qx, qy, _ = k.g2_to_limbs([ref.G2_GEN, q2])
        f = k.pairing_product(
            jnp.asarray(px)[None], jnp.asarray(py)[None],
            jnp.asarray(qx)[None], jnp.asarray(qy)[None],
            jnp.ones((1, 2), bool))
        fs.append(np.asarray(f)[0])
        wants.append(j < n_good)
    return np.stack(fs), np.asarray(wants)


@slow
def test_program_oracle_matches_pairing_is_one():
    fs, wants = _miller_products(2, 2)
    f = jnp.asarray(fs)
    base = np.asarray(k.pairing_is_one(f))
    assert (base == wants).all(), "XLA baseline disagrees with protocol"
    nd = jnp.stack([k.fp12_conj(f), k.FP.normalize(f)])
    if NLIMBS < m.KNL:
        nd = jnp.concatenate(
            [nd, jnp.zeros(nd.shape[:-1] + (m.KNL - NLIMBS,), jnp.int32)],
            axis=-1)
    out = m.run_program_xla(nd)
    num = k.FP.normalize(out[0])
    den = k.FP.normalize(out[1])
    got = np.asarray(k.fp12_eq(num, den))
    assert (got == wants).all()


@slow
def test_mega_kernel_interpret_matches_pairing_is_one():
    fs, wants = _miller_products(2, 1)
    got = np.asarray(m.finalexp_is_one(jnp.asarray(fs), interpret=True))
    assert (got == wants).all()


# == the Miller mega-kernel (same module) ==================================


def _committee_workload():
    """Real aggregated projective inputs: (sig, h, pk) for two shards —
    one fully valid, one with a tampered signature set."""
    tag = b"miller-mega"
    keys = [ref.bls_keygen(tag + bytes([j])) for j in range(3)]
    sigs = [ref.bls_sign(tag, sk) for sk, _ in keys]
    bad = [sigs[0], sigs[1], ref.g1_add(sigs[2], ref.G1_GEN)]
    pks = [pk for _, pk in keys]
    hx, hy, _ = k.g1_to_limbs([ref.hash_to_g1(tag)] * 2)
    sx, sy, sm = k.g1_committee_to_limbs([sigs, bad], 3)
    gx, gy, gm = k.g2_committee_to_limbs([pks, pks], 3)
    sig = k.aggregate_g1_proj(jnp.asarray(sx), jnp.asarray(sy),
                              jnp.asarray(sm))
    pk = k.aggregate_g2_proj(jnp.asarray(gx), jnp.asarray(gy),
                             jnp.asarray(gm))
    return sig, (jnp.asarray(hx), jnp.asarray(hy)), pk


def _f_vals(arr):
    out = np.zeros(arr.shape[:-1], dtype=object)
    for i in range(arr.shape[-1]):
        out = out + (arr[..., i].astype(object) << (12 * i))
    return out % m.P


@slow
def test_miller_oracle_matches_xla_path():
    sig, (hx, hy), pk = _committee_workload()

    def widen(v):
        v = np.asarray(v)
        if v.shape[-1] < m.KNL:
            v = np.concatenate(
                [v, np.zeros(v.shape[:-1] + (m.KNL - v.shape[-1],),
                             np.int32)], axis=-1)
        return v

    want = np.asarray(k._bls_miller_opt(sig, hx, hy, pk))
    got = np.asarray(m.run_miller_xla(
        tuple(widen(v) for v in sig), (widen(hx), widen(hy)),
        tuple(widen(v) for v in pk)))
    assert (_f_vals(want) == _f_vals(got)).all()


@slow
def test_miller_mega_kernel_interpret_matches_xla():
    sig, (hx, hy), pk = _committee_workload()
    want = np.asarray(k._bls_miller_opt(sig, hx, hy, pk))
    got = np.asarray(m.miller_f(sig, hx, hy, pk, interpret=True))
    assert (_f_vals(want) == _f_vals(got)).all()
    # end-to-end boolean parity through the final exponentiation
    assert list(np.asarray(k.pairing_is_one(jnp.asarray(got)))) == \
        [True, False]


@slow
def test_aggregation_mega_kernel_interpret_matches_xla():
    """The tree-reduction kernels reproduce the XLA masked projective
    sums (same rational point: affine cross-multiplication equality),
    and the aggregates verify end-to-end."""
    tag = b"agg-mega"
    keys = [ref.bls_keygen(tag + bytes([j])) for j in range(5)]
    sigs = [ref.bls_sign(tag, sk) for sk, _ in keys]
    pks = [pk for _, pk in keys]
    sx, sy, sm = k.g1_committee_to_limbs([sigs, sigs[:3]], 5)
    gx, gy, gm = k.g2_committee_to_limbs([pks, pks[:3]], 5)
    want_g1 = k.aggregate_g1_proj(jnp.asarray(sx), jnp.asarray(sy),
                                  jnp.asarray(sm))
    got_g1 = m.aggregate_proj(jnp.asarray(sx), jnp.asarray(sy),
                              jnp.asarray(sm), fp2=False, interpret=True)
    want_g2 = k.aggregate_g2_proj(jnp.asarray(gx), jnp.asarray(gy),
                                  jnp.asarray(gm))
    got_g2 = m.aggregate_proj(jnp.asarray(gx), jnp.asarray(gy),
                              jnp.asarray(gm), fp2=True, interpret=True)
    assert np.asarray(k.FP.eq(k.FP.mul(want_g1[0], got_g1[2]),
                              k.FP.mul(got_g1[0], want_g1[2]))).all()
    assert np.asarray(k.FP.eq(k.FP.mul(want_g1[1], got_g1[2]),
                              k.FP.mul(got_g1[1], want_g1[2]))).all()
    assert np.asarray(k.fp2_eq(k.fp2_mul(want_g2[0], got_g2[2]),
                               k.fp2_mul(got_g2[0], want_g2[2]))).all()
    assert np.asarray(k.fp2_eq(k.fp2_mul(want_g2[1], got_g2[2]),
                               k.fp2_mul(got_g2[1], want_g2[2]))).all()
    hx, hy, _ = k.g1_to_limbs([ref.hash_to_g1(tag)] * 2)
    f = k._bls_miller_opt(got_g1, jnp.asarray(hx), jnp.asarray(hy), got_g2)
    assert list(np.asarray(k.pairing_is_one(f))) == [True, True]


@slow
def test_aggregation_mega_kernel_multi_group_batch():
    """Batches above AGG_LANES split into multiple lane groups walked by
    the pallas grid (Mosaic rejects lane blocks smaller than the array's
    lane dim — the r4 TPU probe failure); the grouped path must agree
    with the XLA reduction on every lane, including the pad tail."""
    tag = b"agg-mega-groups"
    keys = [ref.bls_keygen(tag + bytes([j])) for j in range(3)]
    sigs = [ref.bls_sign(tag, sk) for sk, _ in keys]
    B = m.AGG_LANES + 6  # two groups, non-multiple batch -> pad tail
    rows = [sigs if b % 3 else sigs[:2] for b in range(B)]
    sx, sy, sm = k.g1_committee_to_limbs(rows, 3)
    want = k.aggregate_g1_proj(jnp.asarray(sx), jnp.asarray(sy),
                               jnp.asarray(sm))
    got = m.aggregate_proj(jnp.asarray(sx), jnp.asarray(sy),
                           jnp.asarray(sm), fp2=False, interpret=True)
    # cross-multiplication equality is vacuous at Z == 0: first prove no
    # lane came back as the unwritten all-zero block (the exact failure
    # this test guards — a group whose output block is never written)
    assert not np.asarray(k.FP.is_zero(got[2])).any()
    assert np.asarray(k.FP.eq(k.FP.mul(want[0], got[2]),
                              k.FP.mul(got[0], want[2]))).all()
    assert np.asarray(k.FP.eq(k.FP.mul(want[1], got[2]),
                              k.FP.mul(got[1], want[2]))).all()


@slow
def test_aggregation_mega_kernel_chunked_committee():
    """Committees wider than AGG_CHUNK reduce chunk by chunk in the
    kernel (a second grid axis) and fold the chunk sums through the XLA
    addition tree — the audit's 144-slot shape in small: a width that is
    not a chunk multiple (pad tail), ragged masks, one empty chunk."""
    tag = b"agg-mega-chunks"
    keys = [ref.bls_keygen(tag + bytes([j])) for j in range(4)]
    sigs = [ref.bls_sign(tag, sk) for sk, _ in keys]
    pks = [pk for _, pk in keys]
    width = 2 * m.AGG_CHUNK + 3            # three chunks, padded tail
    rows_s = [(sigs * width)[:width], sigs[:3]]
    rows_p = [(pks * width)[:width], pks[:3]]
    sx, sy, sm = k.g1_committee_to_limbs(rows_s, width)
    gx, gy, gm = k.g2_committee_to_limbs(rows_p, width)
    want_g1 = k.aggregate_g1_proj(jnp.asarray(sx), jnp.asarray(sy),
                                  jnp.asarray(sm))
    got_g1 = m.aggregate_proj(jnp.asarray(sx), jnp.asarray(sy),
                              jnp.asarray(sm), fp2=False, interpret=True)
    want_g2 = k.aggregate_g2_proj(jnp.asarray(gx), jnp.asarray(gy),
                                  jnp.asarray(gm))
    got_g2 = m.aggregate_proj(jnp.asarray(gx), jnp.asarray(gy),
                              jnp.asarray(gm), fp2=True, interpret=True)
    assert got_g1[0].shape == want_g1[0].shape
    assert got_g2[0].shape == want_g2[0].shape
    assert not np.asarray(k.FP.is_zero(got_g1[2])).any()
    assert np.asarray(k.FP.eq(k.FP.mul(want_g1[0], got_g1[2]),
                              k.FP.mul(got_g1[0], want_g1[2]))).all()
    assert np.asarray(k.FP.eq(k.FP.mul(want_g1[1], got_g1[2]),
                              k.FP.mul(got_g1[1], want_g1[2]))).all()
    assert np.asarray(k.fp2_eq(k.fp2_mul(want_g2[0], got_g2[2]),
                               k.fp2_mul(got_g2[0], want_g2[2]))).all()
    assert np.asarray(k.fp2_eq(k.fp2_mul(want_g2[1], got_g2[2]),
                               k.fp2_mul(got_g2[1], want_g2[2]))).all()
    hx, hy, _ = k.g1_to_limbs([ref.hash_to_g1(tag)] * 2)
    f = k._bls_miller_opt(got_g1, jnp.asarray(hx), jnp.asarray(hy), got_g2)
    assert list(np.asarray(k.pairing_is_one(f))) == [True, True]
