"""Polynomial-multiproof DAS: PCS properties, batched op, soundness.

The acceptance contracts under test:

- commit/open/verify round-trips for random polynomials; a tampered
  proof, eval, or commitment each verifies False; empty and
  single-index sets and out-of-domain indices behave per contract;
- the multiproof is CONSTANT-SIZE in the sampled index count — one
  64-byte G1 point — and ≥5× smaller than the merkle paths it
  replaces at the default sampling shape;
- batched `das_verify_multiproofs` agrees bit-for-bit with the scalar
  PCS reference across randomized periods including malformed and
  tampered rows — and through the serving and failover backends;
- `SpotCheckSigBackend` catches a silently corrupted multiproof
  verdict and raises `SoundnessViolation` into the breaker path.
"""

import functools
import random

import pytest

from gethsharding_tpu.das import pcs
from gethsharding_tpu.das.pcs import (G1_BYTES, N, commit, dev_srs,
                                      g1_from_bytes, g1_to_bytes,
                                      open_multi, verify_multi)
from gethsharding_tpu.das.poly_proofs import verify_multiproof
from gethsharding_tpu.sigbackend import get_backend


def _values(seed: int, n: int):
    rng = random.Random(seed)
    return [rng.randrange(N) for _ in range(n)]


# -- commit / open / verify properties -------------------------------------


def test_commit_open_verify_roundtrip():
    values = _values(7, 8)
    commitment = commit(values)
    for indices in ((0, 2, 5), (3,)):  # multi-index and single-index
        proof, evals = open_multi(values, indices)
        assert evals == [values[i] for i in indices]
        assert verify_multi(commitment, indices, evals, proof,
                            len(values))
    # empty set: opens to nothing and proves nothing
    proof, evals = open_multi(values, ())
    assert proof is None and evals == []
    assert not verify_multi(commitment, [], [], proof, len(values))


def test_multiproof_is_constant_size_in_m():
    values = _values(11, 32)
    sizes = set()
    for m in (1, 4, 16, 32):
        proof, _ = open_multi(values, range(m))
        sizes.add(len(g1_to_bytes(proof)))
    assert sizes == {G1_BYTES} == {64}


def test_tampered_eval_proof_or_commitment_fails():
    values = _values(13, 6)
    commitment = commit(values)
    indices = (1, 4)
    proof, evals = open_multi(values, indices)
    bad_evals = [evals[0], (evals[1] + 1) % N]
    assert not verify_multi(commitment, indices, bad_evals, proof,
                            len(values))
    bad_proof = pcs.g1_add(proof, pcs.G1_GEN)
    assert not verify_multi(commitment, indices, evals, bad_proof,
                            len(values))
    bad_commitment = pcs.g1_add(commitment, pcs.G1_GEN)
    assert not verify_multi(bad_commitment, indices, evals, proof,
                            len(values))


def test_domain_rejection_is_cheap_and_total():
    """Shape rejection happens before any pairing: out-of-domain,
    duplicate, oversized and ragged sets are False, and the prover
    refuses to open them at all."""
    values = _values(17, 5)
    commitment = commit(values)
    proof, evals = open_multi(values, (2,))
    srs = dev_srs()
    assert not verify_multi(commitment, (5,), evals, proof, 5)  # >= n
    assert not verify_multi(commitment, (-1,), evals, proof, 5)
    assert not verify_multi(commitment, (2, 2), evals * 2, proof, 5)
    assert not verify_multi(commitment, (2,), evals * 2, proof, 5)
    assert not verify_multi(commitment, (2,), [N], proof, 5)  # e >= N
    assert not verify_multi(commitment, (2,), evals, proof, 0)
    assert not verify_multi(commitment, range(srs.max_set + 1),
                            [0] * (srs.max_set + 1), proof, 200)
    with pytest.raises(ValueError):
        open_multi(values, (0, 0))
    with pytest.raises(ValueError):
        open_multi(values, (99,))


def test_g1_wire_roundtrip_and_rejection():
    values = _values(19, 4)
    point = commit(values)
    assert g1_from_bytes(g1_to_bytes(point)) == point
    assert g1_from_bytes(b"\x00" * 64) is None  # infinity
    assert g1_to_bytes(None) == b"\x00" * 64
    with pytest.raises(ValueError):
        g1_from_bytes(b"\x01" * 63)  # wrong length
    with pytest.raises(ValueError):
        g1_from_bytes(b"\x01" * 64)  # off-curve
    # the bytes-face verifier turns decode failures into verdicts
    assert not verify_multiproof(b"\x01" * 63, [0], [values[0]],
                                 b"\x00" * 64, 4)
    assert not verify_multiproof(g1_to_bytes(point), [0], [values[0]],
                                 b"garbage", 4)


# -- the batched op, bit-for-bit and through the backend layers ------------


@functools.lru_cache(maxsize=1)
def _poly_rows():
    """(commitments, index_rows, eval_rows, proofs, ns) rows: honest
    openings from randomized periods plus every malformed-row class,
    all in wire (bytes) form. Cached — scalar pairing checks are the
    expensive part of this file."""
    rows = []
    for seed, n, indices in ((101, 7, (0, 3, 6)), (102, 5, (1,)),
                             (103, 9, (2, 4, 7, 8))):
        values = _values(seed, n)
        commitment = g1_to_bytes(commit(values))
        proof, evals = open_multi(values, indices)
        rows.append((commitment, list(indices), evals,
                     g1_to_bytes(proof), n))
    values = _values(104, 6)
    commitment = g1_to_bytes(commit(values))
    proof, evals = open_multi(values, (1, 3))
    good = (commitment, [1, 3], evals, g1_to_bytes(proof), 6)
    rows += [
        # tampered eval / tampered proof bytes / tampered commitment
        (good[0], good[1], [evals[0], (evals[1] + 1) % N], good[3], 6),
        (good[0], good[1], evals,
         g1_to_bytes(pcs.g1_add(proof, pcs.G1_GEN)), 6),
        (g1_to_bytes(pcs.g1_add(commit(values), pcs.G1_GEN)),
         good[1], evals, good[3], 6),
        (b"\x07" * 64, good[1], evals, good[3], 6),   # off-curve C
        (good[0], good[1], evals, good[3][:32], 6),   # short proof
        (good[0], [1, 1], evals, good[3], 6),         # dup indices
        (good[0], [], [], good[3], 6),                # empty set
        (good[0], [1, 9], evals, good[3], 6),         # out of domain
    ]
    # the degenerate-pairing row: a constant polynomial's quotient is
    # zero, so π is the G1 infinity — must still verify True
    const = [42] * 4
    c_proof, c_evals = open_multi(const, (0, 2))
    rows.append((g1_to_bytes(commit(const)), [0, 2], c_evals,
                 g1_to_bytes(c_proof), 4))
    return tuple(map(tuple, zip(*rows)))


@functools.lru_cache(maxsize=1)
def _poly_want():
    return tuple(get_backend("python").das_verify_multiproofs(
        *[list(col) for col in _poly_rows()]))


def test_das_verify_multiproofs_scalar_vs_jax_bit_for_bit():
    cols = [list(col) for col in _poly_rows()]
    want = list(_poly_want())
    assert want == [True] * 3 + [False] * 8 + [True]
    jax_backend = get_backend("jax")
    got = jax_backend.das_verify_multiproofs(*cols)
    assert got == want
    ledger = jax_backend.last_wire
    assert ledger["op"] == "das_verify_multiproofs"
    assert ledger["rows"] == len(cols[0])
    assert ledger["wire_bytes"] > 0
    # empty batch: no dispatch, clean ledger
    assert jax_backend.das_verify_multiproofs([], [], [], [], []) == []
    assert jax_backend.last_wire is None


def test_das_verify_multiproofs_through_serving_and_failover():
    from gethsharding_tpu.resilience.breaker import FailoverSigBackend
    from gethsharding_tpu.serving import ServingSigBackend
    from gethsharding_tpu.serving.batcher import SERVING_OPS

    assert "das_verify_multiproofs" in SERVING_OPS
    cols = [list(col) for col in _poly_rows()]
    want = list(_poly_want())
    serving = ServingSigBackend(get_backend("jax"))
    try:
        assert serving.das_verify_multiproofs(*cols) == want
        counts = serving.batcher.dispatch_counts
        assert counts["das_verify_multiproofs"] == 1
    finally:
        serving.close()
    failover = FailoverSigBackend(get_backend("jax"),
                                  get_backend("python"))
    assert failover.das_verify_multiproofs(*cols) == want


def test_spotcheck_catches_corrupted_multiproof_verdict():
    """A backend that silently flips a multiproof verdict is caught by
    the soundness spot-checker, and the violation trips the failover
    breaker so the scalar fallback serves correct verdicts."""
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.resilience.breaker import (CircuitBreaker,
                                                     FailoverSigBackend)
    from gethsharding_tpu.resilience.chaos import (ChaosSigBackend,
                                                   parse_spec)
    from gethsharding_tpu.resilience.errors import SoundnessViolation
    from gethsharding_tpu.resilience.soundness import SpotCheckSigBackend

    values = _values(211, 6)
    commitment = g1_to_bytes(commit(values))
    proof, evals = open_multi(values, (0, 2, 5))
    cols = ([commitment], [[0, 2, 5]], [evals], [g1_to_bytes(proof)],
            [6])
    schedule = parse_spec(
        "seed=3,backend.das_verify_multiproofs:mode=corrupt")
    corrupt = ChaosSigBackend(get_backend("python"), schedule)
    audited = SpotCheckSigBackend(corrupt, rate=1.0, rows=1,
                                  registry=Registry())
    with pytest.raises(SoundnessViolation):
        audited.das_verify_multiproofs(*[list(c) for c in cols])
    # the production shape: the violation is a primary fault
    registry = Registry()
    backend = FailoverSigBackend(
        SpotCheckSigBackend(
            ChaosSigBackend(
                get_backend("python"),
                parse_spec(
                    "seed=3,backend.das_verify_multiproofs:mode=corrupt")),
            rate=1.0, rows=1, registry=registry),
        get_backend("python"),
        breaker=CircuitBreaker(name="das-poly-test", fault_threshold=1,
                               reset_s=60.0, registry=registry),
        registry=registry)
    got = backend.das_verify_multiproofs(*[list(c) for c in cols])
    assert got == [True]
    assert backend.breaker.state_name == "open"


# -- the proof-byte economics ----------------------------------------------


def test_poly_proof_bytes_are_constant_and_5x_smaller():
    from gethsharding_tpu.das.sampler import proof_bytes, soundness_table

    assert proof_bytes(16, "poly") == proof_bytes(64, "poly") == 64
    assert proof_bytes(0, "poly") == 0
    assert proof_bytes(16, "merkle") == 16 * 8 * 32
    # the ISSUE acceptance floor at the default sampling shape
    assert proof_bytes(16, "merkle") >= 5 * proof_bytes(16, "poly")
    with pytest.raises(ValueError):
        proof_bytes(16, "zk-starks")
    rows = soundness_table(n=255, k_data=170, ks=(4, 16))
    for row in rows:
        assert row["merkle_proof_bytes"] == row["k"] * 8 * 32
        assert row["poly_proof_bytes"] == 64
        assert 0.0 < row["p_detect"] <= 1.0


# -- the host's coefficients (ISSUE 39) --------------------------------------


@pytest.mark.parametrize("m", [1, 2, 16, 64])
def test_row_coeffs_equal_the_reference_coefficients(m):
    from gethsharding_tpu.das.poly_proofs import row_coeffs

    rng = random.Random(3900 + m)
    xs = rng.sample(range(255), m)
    ys = [rng.randrange(N) for _ in range(m)]
    ys[0] = 0                       # a zero evaluation adds nothing
    r, z = row_coeffs(xs, ys)
    assert r == pcs.lagrange_coeffs(xs, ys)
    assert z == pcs.vanishing_coeffs(xs)


def test_marshal_keeps_malformed_rows_off_the_msm_count():
    from gethsharding_tpu.das.poly_proofs import marshal_multiproofs

    cols = [list(col) for col in _poly_rows()]
    st = marshal_multiproofs(*cols, 16)
    assert st["msm_rows"] == sum(st["valid"]) == 7
    assert st["terms"] == 4         # the widest well-shaped row: 4 indices
    assert st["r_digits"].shape[:2] == (16, 4)
    assert st["z_digits"].shape[:2] == (16, 5)
    assert not st["r_digits"][~st["valid"]].any()
