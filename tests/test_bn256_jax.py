"""Differential tests: batched pairing kernel (ops/bn256_jax) vs the scalar
reference (crypto/bn256.py, itself EIP-196/197-parameterized and
golden-tested in tests/test_bn256.py).

Raw Miller outputs are NOT comparable (the kernel's inversion-free lines
carry Fp2 scale factors the final exponentiation kills), so comparisons
happen at pairing value / PairingCheck / BLS-verify level — exactly the
surfaces the framework consumes.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from gethsharding_tpu.crypto import bn256 as ref
from gethsharding_tpu.ops import bn256_jax as k
from gethsharding_tpu.ops.limb import NLIMBS, ints_to_limbs

# The full Miller-loop/final-exponentiation kernels take ~20-90 s each to
# compile on XLA:CPU (near-instant on repeat runs via the persistent cache
# in conftest.py). They run by default — the suite must exercise the
# north-star kernel end to end — but GETHSHARDING_SKIP_SLOW=1 skips them
# for quick local loops.
slow = pytest.mark.skipif(
    os.environ.get("GETHSHARDING_SKIP_SLOW") == "1",
    reason="GETHSHARDING_SKIP_SLOW=1",
)


def _rand_fp12(rng) -> ref.Fp12:
    def fp2():
        return ref.Fp2(int(rng.integers(0, 1 << 62)) * int(rng.integers(0, 1 << 62)) % ref.P,
                       int(rng.integers(0, 1 << 62)) % ref.P)
    def fp6():
        return ref.Fp6(fp2(), fp2(), fp2())
    return ref.Fp12(fp6(), fp6())


def _fp12_to_arr(x: ref.Fp12) -> np.ndarray:
    """Scalar Fp12 -> the kernel's w-basis (6, 2, 22) layout."""
    tower = np.zeros((2, 3, 2, NLIMBS), np.int32)
    for h, c6 in enumerate((x.c0, x.c1)):
        for l, c2 in enumerate((c6.c0, c6.c1, c6.c2)):
            tower[h, l, 0] = ints_to_limbs([c2.a])[0]
            tower[h, l, 1] = ints_to_limbs([c2.b])[0]
    return k.fp12_from_tower(tower)


def _arr_to_coeffs(arr) -> np.ndarray:
    return k.fp12_to_int_coeffs(arr)


def _fp12_coeffs(x: ref.Fp12) -> np.ndarray:
    out = np.zeros((2, 3, 2), object)
    for h, c6 in enumerate((x.c0, x.c1)):
        for l, c2 in enumerate((c6.c0, c6.c1, c6.c2)):
            out[h, l, 0], out[h, l, 1] = c2.a, c2.b
    return out


def test_fp12_mul_inv_matches_scalar():
    rng = np.random.default_rng(1)
    a, b = _rand_fp12(rng), _rand_fp12(rng)
    arr = jnp.asarray(np.stack([_fp12_to_arr(a), _fp12_to_arr(b)]))
    prod = np.asarray(_arr_to_coeffs(k.fp12_mul(arr[0], arr[1])))
    assert (prod == _fp12_coeffs(a * b)).all()
    inv = np.asarray(_arr_to_coeffs(jax.jit(k.fp12_inv)(arr[0])))
    assert (inv == _fp12_coeffs(a.inv())).all()


@pytest.mark.parametrize("n", [1, 2, 3])
def test_frobenius_matches_scalar_pow(n):
    rng = np.random.default_rng(10 + n)
    a = _rand_fp12(rng)
    got = np.asarray(_arr_to_coeffs(
        k.fp12_frobenius(jnp.asarray(_fp12_to_arr(a)), n)))
    expect = _fp12_coeffs(a.pow(ref.P ** n))
    assert (got == expect).all()


@slow
def test_final_exponentiation_matches_scalar():
    rng = np.random.default_rng(2)
    a = _rand_fp12(rng)
    got = np.asarray(_arr_to_coeffs(
        jax.jit(k.final_exponentiation)(jnp.asarray(_fp12_to_arr(a)))))
    expect = _fp12_coeffs(a.pow(ref.FINAL_EXP))
    assert (got == expect).all()


@slow
def test_pairing_value_matches_scalar():
    g1 = ref.g1_mul(7, ref.G1_GEN)
    g2 = ref.g2_mul(11, ref.G2_GEN)
    px, py, _ = k.g1_to_limbs([g1])
    qx, qy, _ = k.g2_to_limbs([g2])
    f = k.final_exponentiation(
        k.miller_loop(jnp.asarray(px[0]), jnp.asarray(py[0]),
                      jnp.asarray(qx[0]), jnp.asarray(qy[0])))
    got = np.asarray(_arr_to_coeffs(f))
    expect = _fp12_coeffs(ref.pairing(g1, g2))
    assert (got == expect).all()


@slow
def test_pairing_check_parity_batch():
    # rows: [bilinear identity: e(aP, Q)·e(-P, aQ) = 1] and [broken pair]
    a = 123456789
    p1, q1 = ref.g1_mul(a, ref.G1_GEN), ref.G2_GEN
    p2, q2 = ref.g1_neg(ref.G1_GEN), ref.g2_mul(a, ref.G2_GEN)
    bad_p2 = ref.g1_neg(ref.g1_mul(2, ref.G1_GEN))
    rows_p = [[p1, p2], [p1, bad_p2]]
    rows_q = [[q1, q2], [q1, q2]]
    px, py, qx, qy = [], [], [], []
    for rp, rq in zip(rows_p, rows_q):
        x1, y1, _ = k.g1_to_limbs(rp)
        x2, y2, _ = k.g2_to_limbs(rq)
        px.append(x1), py.append(y1), qx.append(x2), qy.append(y2)
    mask = np.ones((2, 2), bool)
    got = np.asarray(jax.jit(k.pairing_check)(
        jnp.asarray(np.stack(px)), jnp.asarray(np.stack(py)),
        jnp.asarray(np.stack(qx)), jnp.asarray(np.stack(qy)),
        jnp.asarray(mask)))
    expect = [ref.pairing_check(list(zip(rp, rq)))
              for rp, rq in zip(rows_p, rows_q)]
    assert list(got) == expect == [True, False]


@slow
def test_pairing_check_infinity_mask():
    # an infinity pair contributes identity, matching the scalar skip rule
    a = 5
    p1 = ref.g1_mul(a, ref.G1_GEN)
    p2 = ref.g1_neg(ref.G1_GEN)
    q2 = ref.g2_mul(a, ref.G2_GEN)
    px, py, pok = k.g1_to_limbs([p1, None, p2])
    qx, qy, qok = k.g2_to_limbs([ref.G2_GEN, ref.G2_GEN, q2])
    mask = pok & qok
    got = np.asarray(k.pairing_check(
        jnp.asarray(px)[None], jnp.asarray(py)[None],
        jnp.asarray(qx)[None], jnp.asarray(qy)[None],
        jnp.asarray(mask)[None]))
    assert got[0] == ref.pairing_check(
        [(p1, ref.G2_GEN), (None, ref.G2_GEN), (p2, q2)]) == True  # noqa: E712


@slow
def test_bls_aggregate_batch_matches_scalar():
    header = b"collation-header-hash"
    committee = [ref.bls_keygen(bytes([i])) for i in range(4)]
    sigs = [ref.bls_sign(header, sk) for sk, _ in committee]
    agg_sig = ref.bls_aggregate_sigs(sigs)
    agg_pk = ref.bls_aggregate_pks([pk for _, pk in committee])
    h = ref.hash_to_g1(header)
    tampered = ref.g1_add(agg_sig, ref.G1_GEN)

    hx, hy, _ = k.g1_to_limbs([h, h])
    sx, sy, _ = k.g1_to_limbs([agg_sig, tampered])
    pkx, pky, _ = k.g2_to_limbs([agg_pk, agg_pk])
    got = np.asarray(jax.jit(k.bls_verify_aggregate_batch)(
        jnp.asarray(hx), jnp.asarray(hy), jnp.asarray(sx), jnp.asarray(sy),
        jnp.asarray(pkx), jnp.asarray(pky), jnp.asarray([True, True])))
    assert list(got) == [True, False]
    assert ref.bls_verify(header, agg_sig, agg_pk) is True
    assert ref.bls_verify(header, tampered, agg_pk) is False


def test_fp12_sqr_matches_mul():
    """Complex squaring must equal the generic product (fast, always on)."""
    rng = np.random.default_rng(3)
    a = _rand_fp12(rng)
    arr = jnp.asarray(_fp12_to_arr(a))
    sq = np.asarray(_arr_to_coeffs(jax.jit(k.fp12_sqr)(arr)))
    assert (sq == _fp12_coeffs(a * a)).all()


@slow
def test_committee_aggregation_matches_host():
    """Device projective tree-sum == host point addition, including the
    complete-formula corner cases: identity padding, duplicate points
    (doubling), and an inverse pair that cancels to infinity."""
    rows = []
    base = [ref.g1_mul(7 + i, ref.G1_GEN) for i in range(6)]
    rows.append(base)                     # plain sum
    rows.append([base[0], base[0]])       # doubling
    rows.append([base[1], ref.g1_neg(base[1])])  # cancels to infinity
    rows.append([base[2]])                # single point
    xs, ys, mask = k.g1_committee_to_limbs(rows, 8)
    X, Y, Z = jax.jit(k.aggregate_g1_proj)(
        jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask))
    Xi, Yi, Zi = (k.FP.to_ints(v) for v in (X, Y, Z))
    for b, row in enumerate(rows):
        host = ref.bls_aggregate_sigs(row)
        if host is None:
            assert int(Zi[b]) % ref.P == 0
            continue
        zinv = pow(int(Zi[b]), ref.P - 2, ref.P)
        assert (int(Xi[b]) * zinv % ref.P,
                int(Yi[b]) * zinv % ref.P) == host


@slow
def test_g2_committee_aggregation_matches_host():
    """The Fp2 reduction branch (distinct b3' = 9/xi constant) against
    host G2 addition, incl. doubling, cancellation, identity padding."""
    base = [ref.g2_mul(11 + i, ref.G2_GEN) for i in range(5)]
    rows = [base,
            [base[0], base[0]],
            [base[1], ref.g2_neg(base[1])],
            [base[2]]]
    xs, ys, mask = k.g2_committee_to_limbs(rows, 8)
    X, Y, Z = jax.jit(k.aggregate_g2_proj)(
        jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask))
    Xi, Yi, Zi = (k.FP.to_ints(np.asarray(k.FP.canon(v)))
                  for v in (X, Y, Z))
    for b, row in enumerate(rows):
        host = ref.bls_aggregate_pks(row)
        zc = ref.Fp2(int(Zi[b][0]), int(Zi[b][1]))
        if host is None:
            assert zc.is_zero()
            continue
        zinv = zc.inv()
        got = (ref.Fp2(int(Xi[b][0]), int(Xi[b][1])) * zinv,
               ref.Fp2(int(Yi[b][0]), int(Yi[b][1])) * zinv)
        assert got == host


@slow
def test_committee_verify_rejects_cancelled_aggregates():
    """Adversarial cancellation: a non-empty row whose signatures (or
    pubkeys) sum to infinity must be rejected, not vacuously accepted."""
    tag = b"cancel"
    keys = [ref.bls_keygen(tag + bytes([j])) for j in range(2)]
    sigs = [ref.bls_sign(tag, sk) for sk, _ in keys]
    pks = [pk for _, pk in keys]
    rows_sig = [[sigs[0], ref.g1_neg(sigs[0])],   # sig aggregate = inf
                sigs]                              # pk aggregate = inf
    rows_pk = [pks,
               [pks[0], ref.g2_neg(pks[0])]]
    msgs = [tag, tag]
    hx, hy, hok = k.g1_to_limbs([ref.hash_to_g1(m) for m in msgs])
    sx, sy, sm = k.g1_committee_to_limbs(rows_sig, 2)
    px, py, pm = k.g2_committee_to_limbs(rows_pk, 2)
    out = jax.jit(k.bls_aggregate_verify_committee_batch)(
        jnp.asarray(hx), jnp.asarray(hy), jnp.asarray(sx), jnp.asarray(sy),
        jnp.asarray(sm), jnp.asarray(px), jnp.asarray(py), jnp.asarray(pm),
        jnp.asarray(hok))
    assert [bool(v) for v in np.asarray(out)] == [False, False]


@slow
def test_tree_reduce_non_power_of_two_width():
    """Widths that are not powers of two reduce via binary segment
    decomposition — same sum as the host, no dropped points."""
    pts = [ref.g1_mul(3 + i, ref.G1_GEN) for i in range(6)]
    xs, ys, mask = k.g1_committee_to_limbs([pts, pts[:5]], 6)
    X, Y, Z = jax.jit(k.aggregate_g1_proj)(
        jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask))
    Xi, Yi, Zi = (k.FP.to_ints(v) for v in (X, Y, Z))
    for b, row in enumerate([pts, pts[:5]]):
        host = ref.bls_aggregate_sigs(row)
        zinv = pow(int(Zi[b]), ref.P - 2, ref.P)
        assert (int(Xi[b]) * zinv % ref.P,
                int(Yi[b]) * zinv % ref.P) == host
