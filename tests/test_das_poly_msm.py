"""The multiproof check with both MSMs on the device (ISSUE 39).

- `fixed_base_msm` over the SRS's device-built tables equals the scalar
  `pcs.g1_msm` / `pcs.g2_msm` for scalars 0, 1, N - 1, random ones, an
  all-zero row and a row shorter than the set width;
- the whole op equals the scalar reference over true, tampered-eval,
  foreign-proof, malformed and degenerate rows, the degenerate ones
  (A, π or Z at infinity) decided on the device;
- the served op calls neither `pcs.g1_msm` nor `pcs.g2_msm`, builds the
  tables once and ships them as arguments.

Verdicts are booleans: every one must match, no tolerance.
"""

import functools
import random

import numpy as np
import pytest

from gethsharding_tpu.crypto import bn256 as ref
from gethsharding_tpu.das import pcs
from gethsharding_tpu.das.pcs import N, commit, g1_to_bytes, open_multi
from gethsharding_tpu.sigbackend import get_backend

SCALAR_ROWS = [
    [0, 1, N - 1, 2],
    [random.Random(39).randrange(N) for _ in range(4)],
    [0, 0, 0, 0],
    [N - 1, N - 2],             # shorter than the set width: zero-padded
]


@pytest.fixture(scope="module")
def backend():
    return get_backend("jax")


@functools.lru_cache(maxsize=1)
def _device_msms():
    """Both MSMs of SCALAR_ROWS from the backend's tables, as affine
    points (None = infinity)."""
    import jax

    from gethsharding_tpu.ops import bn256_jax as bj

    g1_table, g2_table = get_backend("jax")._srs_tables()

    def msms(t1, t2, digits):
        return (bj.fixed_base_msm(t1, digits, bj._g1_proj_add),
                bj.fixed_base_msm(t2, digits, bj._g2_proj_add))

    g1, g2 = jax.jit(msms)(g1_table, g2_table,
                           bj.msm_digits(SCALAR_ROWS, 4))
    X, Y, Z = (bj.FP.to_ints(c) for c in g1)
    out1 = []
    for x, y, z in zip(X, Y, Z):
        zi = pow(int(z), -1, ref.P) if int(z) else None
        out1.append(None if zi is None
                    else (int(x) * zi % ref.P, int(y) * zi % ref.P))
    out2 = []
    for b in range(len(SCALAR_ROWS)):
        x, y, z = (ref.Fp2(*(int(v) for v in bj.FP.to_ints(c[b])))
                   for c in g2)
        out2.append(None if z.is_zero() else (x * z.inv(), y * z.inv()))
    return out1, out2


@pytest.mark.parametrize("row", range(len(SCALAR_ROWS)))
def test_device_g1_msm_equals_the_scalar_msm(row):
    srs = pcs.dev_srs()
    assert _device_msms()[0][row] == pcs.g1_msm(SCALAR_ROWS[row],
                                                 srs.g1_powers)


@pytest.mark.parametrize("row", range(len(SCALAR_ROWS)))
def test_device_g2_msm_equals_the_scalar_msm(row):
    srs = pcs.dev_srs()
    assert _device_msms()[1][row] == pcs.g2_msm(SCALAR_ROWS[row],
                                                 srs.g2_powers)


def test_digits_recompose_their_scalars():
    from gethsharding_tpu.ops.bn256_jax import (MSM_WINDOW, MSM_WINDOWS,
                                                msm_digits)

    digits = msm_digits(SCALAR_ROWS + [None], 4)
    assert digits.shape == (5, 4, MSM_WINDOWS) and digits.dtype == np.uint8
    assert int(digits.max()) < 1 << MSM_WINDOW
    for b, row in enumerate(SCALAR_ROWS + [None]):
        want = list(row or ()) + [0] * (4 - len(row or ()))
        got = [sum(int(d) << (MSM_WINDOW * j) for j, d in enumerate(ds))
               for ds in digits[b]]
        assert got == want


# -- the whole op -------------------------------------------------------------


def _values(seed, n):
    rng = random.Random(seed)
    return [rng.randrange(N) for _ in range(n)]


@functools.lru_cache(maxsize=1)
def _rows():
    """(name, row) pairs in wire form: honest rows, each fault of the
    benchmark's builder, malformed rows and the degenerate ones."""
    rows = []
    honest = []
    for seed, n, idx in ((391, 8, (0, 3, 5, 7)), (392, 6, (2,)),
                         (393, 7, (1, 4, 6))):
        values = _values(seed, n)
        proof, evals = open_multi(values, idx)
        honest.append((g1_to_bytes(commit(values)), list(idx), evals,
                       g1_to_bytes(proof), n))
    rows += [(f"true_{i}", row) for i, row in enumerate(honest)]
    c, idx, evals, proof, n = honest[0]
    rows += [
        ("tampered_eval", (c, idx, [evals[0] ^ 1] + evals[1:], proof, n)),
        ("foreign_proof", (c, idx, evals, honest[2][3], n)),
        ("failed_fetch", (c, idx, [0] * len(idx), b"", n)),
        ("off_curve_commitment", (b"\x07" * 64, idx, evals, proof, n)),
        ("duplicate_index", (c, [0, 0, 5, 7], evals, proof, n)),
        ("index_outside_domain", (c, [0, 3, 5, n], evals, proof, n)),
        ("eval_outside_field", (c, idx, [N] + evals[1:], proof, n)),
    ]
    # a constant polynomial: C = R, so A is at infinity, and its
    # quotient is zero, so π is too: both pairs skipped, True
    const = [42] * 4
    c_proof, c_evals = open_multi(const, (0, 2))
    cc = g1_to_bytes(commit(const))
    rows += [
        ("a_and_pi_at_infinity", (cc, [0, 2], c_evals,
                                  g1_to_bytes(c_proof), 4)),
        # A at infinity, π not: the second pair alone never pairs to 1
        ("a_at_infinity_pi_not", (cc, [0, 2], c_evals,
                                  g1_to_bytes(pcs.G1_GEN), 4)),
        # π at infinity, A not: the first pair alone never pairs to 1
        ("pi_at_infinity_a_not", (c, idx, evals, b"\x00" * 64, n)),
        # C at infinity (the zero polynomial): honest, True
        ("zero_polynomial", (g1_to_bytes(None), [1, 2], [0, 0],
                             g1_to_bytes(open_multi([0] * 4, (1, 2))[0]),
                             4)),
    ]
    return tuple(rows)


def _cols():
    return [list(col) for col in zip(*(row for _, row in _rows()))]


@functools.lru_cache(maxsize=1)
def _want():
    return tuple(get_backend("python").das_verify_multiproofs(*_cols()))


@functools.lru_cache(maxsize=1)
def _got():
    return tuple(get_backend("jax").das_verify_multiproofs(*_cols()))


def test_the_reference_agrees_with_the_construction():
    truth = {"true_0", "true_1", "true_2", "a_and_pi_at_infinity",
             "zero_polynomial"}
    assert list(_want()) == [name in truth for name, _ in _rows()]


@pytest.mark.parametrize("row", [name for name, _ in _rows()])
def test_the_device_verdict_equals_the_reference(row):
    at = [name for name, _ in _rows()].index(row)
    assert _got()[at] == _want()[at]


def test_a_z_at_infinity_is_decided_by_the_scalar_rule(backend):
    """Z = [z_S(τ)]₂ is at infinity only where τ is in S, which no
    honest row reaches: feed the kernel an all-zero vanishing plane.
    A at infinity with Z at infinity skips both pairs (True); A not at
    infinity with Z at infinity leaves one pair that never pairs to 1
    (False); valid=False stays False."""
    import jax.numpy as jnp

    from gethsharding_tpu.das import poly_proofs

    const = [42] * 4
    c_proof, c_evals = open_multi(const, (0, 2))
    values = _values(394, 4)
    proof, evals = open_multi(values, (0, 2))
    cols = [[g1_to_bytes(commit(const)), g1_to_bytes(commit(values)),
             g1_to_bytes(commit(values))], [[0, 2]] * 3,
            [c_evals, evals, evals],
            [g1_to_bytes(pcs.G1_GEN), g1_to_bytes(proof),
             g1_to_bytes(proof)], [4, 4, 4]]
    # beside a four-index row, at the bucket of `_cols()`: the shape
    # the op's own tests compiled
    for col, value in zip(cols, _rows()[0][1]):
        col.append(value)
    st = poly_proofs.marshal_multiproofs(
        *cols, backend._bucket(len(_rows())))
    valid = st["valid"].copy()
    valid[2] = False
    planes = (st["cx"], st["cy"], st["c_inf"], st["px"], st["py"],
              st["p_inf"], st["r_digits"], np.zeros_like(st["z_digits"]),
              valid)
    got = backend._das_poly(*(jnp.asarray(p) for p in planes),
                            *backend._srs_tables())
    assert [bool(v) for v in np.asarray(got)[:3]] == [True, False, False]


def test_the_served_op_runs_no_host_msm(monkeypatch):
    from gethsharding_tpu.serving import ServingSigBackend

    want = list(_want())

    def refuse(*_):
        raise AssertionError("a host MSM on the served path")

    monkeypatch.setattr(pcs, "g1_msm", refuse)
    monkeypatch.setattr(pcs, "g2_msm", refuse)
    serving = ServingSigBackend(get_backend("jax"))
    try:
        assert serving.das_verify_multiproofs(*_cols()) == want
    finally:
        serving.close()


def test_the_tables_are_built_once_and_held_as_arguments(backend):
    from gethsharding_tpu import metrics
    from gethsharding_tpu.ops.bn256_jax import MSM_WINDOW, MSM_WINDOWS

    first = backend._srs_tables()
    backend.das_verify_multiproofs(*_cols())
    misses = metrics.counter("jax/compile_cache/misses").value
    backend.das_verify_multiproofs(*_cols())
    assert backend._srs_tables() is first
    assert metrics.counter("jax/compile_cache/misses").value == misses
    g1_table, g2_table = first
    entries = MSM_WINDOWS << MSM_WINDOW
    assert g1_table.shape[:2] == (pcs.MAX_MULTIPROOF_INDICES * entries, 3)
    assert g2_table.shape[:3] == ((pcs.MAX_MULTIPROOF_INDICES + 1)
                                  * entries, 3, 2)
    # resident, censused with the generator line table
    held = {id(b) for b in backend._resident_buffers()}
    assert id(g1_table) in held and id(g2_table) in held


def test_a_small_srs_caps_the_set_width_at_its_g2_powers(monkeypatch):
    """An SRS of degree 6 holds 7 G2 powers (max_set 6): a 6-index set
    has a set width of 6, whose z of 7 coefficients reads every G2
    power and no slot past the table, and the verdicts stay exact."""
    from gethsharding_tpu.das import poly_proofs

    monkeypatch.setenv("GETHSHARDING_DAS_SRS_SIZE", "6")
    assert pcs.dev_srs().max_set == 6
    values = _values(395, 7)
    idx = [0, 1, 2, 3, 5, 6]
    proof, evals = open_multi(values, idx)
    c = g1_to_bytes(commit(values))
    cols = [[c, c], [idx, idx], [evals, [evals[0] ^ 1] + evals[1:]],
            [g1_to_bytes(proof)] * 2, [7, 7]]
    assert poly_proofs.marshal_multiproofs(*cols, 2)["terms"] == 6
    want = get_backend("python").das_verify_multiproofs(*cols)
    assert want == [True, False]
    assert get_backend("jax").das_verify_multiproofs(*cols) == want
