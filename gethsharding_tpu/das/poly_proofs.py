"""Batched DAS multiproof verification: scalar truth + fixed-shape planes.

The `das_verify_multiproofs` SigBackend op. One ROW is one sampled
collation in a period: a 64-byte G1 commitment, the sampled index set,
the claimed chunk-value evaluations, ONE 64-byte G1 multiproof, and
the collation's domain size n. The verdict is `pcs.verify_multi` —
does e(C − [r(τ)]₁, H)·e(−π, [z_S(τ)]₂) == 1.

`verify_multiproofs` is the scalar batch face
(`PythonSigBackend.das_verify_multiproofs`) and THE differential
reference. `marshal_multiproofs` keeps per row only what the host must
do: the shape checks, the decode of the two wire points, and the
row's scalars — the interpolation coefficients of r and the vanishing
coefficients of z_S (`row_coeffs`, O(m²)) as w-bit digit planes. The
device (`ops/bn256_jax.das_poly_verify_batch`) sums both MSMs from the
SRS's resident fixed-base tables, folds A = C − [r(τ)]₁, and runs the
committee kernel's projective pairing check.

Bit-identity with the scalar path is BY CONSTRUCTION: every scalar
rejection (bad shapes, undecodable or off-curve wire points) becomes
`valid=False` at marshal time, and the rows with a point at infinity
(A, π or Z — e.g. a constant polynomial's zero quotient) are decided on
the device by the scalar pairing's own rule for skipped pairs.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from gethsharding_tpu import metrics, tracing
from gethsharding_tpu.das import pcs

# re-exported caps: the service/sampler size their index sets by these
MAX_MULTIPROOF_INDICES = pcs.MAX_MULTIPROOF_INDICES
PROOF_BYTES = pcs.PROOF_BYTES

# the coefficients and their digit planes, inside sig/host_marshal_time
_T_POLY_COEFFS = metrics.timer("sig/poly_coeffs_time")


def verify_multiproof(commitment: bytes, indices: Sequence[int],
                      evals: Sequence[int], proof: bytes, n: int,
                      srs: Optional[pcs.SRS] = None) -> bool:
    """One row verdict from wire-form (64-byte) G1 points. THE
    reference semantics: undecodable points are False, never raise."""
    srs = srs or pcs.dev_srs()
    try:
        c_point = pcs.g1_from_bytes(commitment)
        p_point = pcs.g1_from_bytes(proof)
    except (TypeError, ValueError):
        return False
    return pcs.verify_multi(c_point, indices, evals, p_point, n, srs)


def verify_multiproofs(commitments: Sequence[bytes],
                       index_rows: Sequence[Sequence[int]],
                       eval_rows: Sequence[Sequence[int]],
                       proofs: Sequence[bytes],
                       ns: Sequence[int]) -> List[bool]:
    """The scalar batch face (`PythonSigBackend.das_verify_multiproofs`)."""
    srs = pcs.dev_srs()
    return [verify_multiproof(c, idx, ev, pf, n, srs)
            for c, idx, ev, pf, n
            in zip(commitments, index_rows, eval_rows, proofs, ns)]


def row_coeffs(xs: Sequence[int], ys: Sequence[int]):
    """(r, z): the coefficients of the interpolation of (x_i, y_i) and
    of the vanishing polynomial z_S, low-order first, mod N — equal to
    `pcs.lagrange_coeffs` and `pcs.vanishing_coeffs`, in O(m²): z_S
    once, each Lagrange numerator z_S / (x − x_i) by synthetic
    division, one inversion for all m denominators."""
    n_mod = pcs.N
    z = pcs.vanishing_coeffs(xs)
    m = len(xs)
    denoms = []
    for i, xi in enumerate(xs):
        d = 1
        for j, xj in enumerate(xs):
            if j != i:
                d = d * (xi - xj) % n_mod
        denoms.append(d)
    # Montgomery's trick: prefix products, one inverse, walk back
    prefix = [1]
    for d in denoms:
        prefix.append(prefix[-1] * d % n_mod)
    inv = pow(prefix[-1], -1, n_mod)
    scales = [0] * m
    for i in range(m - 1, -1, -1):
        scales[i] = ys[i] * inv * prefix[i] % n_mod
        inv = inv * denoms[i] % n_mod
    acc = [0] * m
    for xi, scale in zip(xs, scales):
        if not scale:
            continue
        q = z[m]                      # q_{m-1}; q_{k-1} = z_k + x_i·q_k
        acc[m - 1] += scale * q
        for k in range(m - 1, 0, -1):
            q = (z[k] + xi * q) % n_mod
            acc[k - 1] += scale * q
    return [a % n_mod for a in acc], z


def marshal_multiproofs(commitments: Sequence[bytes],
                        index_rows: Sequence[Sequence[int]],
                        eval_rows: Sequence[Sequence[int]],
                        proofs: Sequence[bytes],
                        ns: Sequence[int], bucket: int) -> dict:
    """Rows -> the fixed (bucket, ...) planes of
    `ops/bn256_jax.das_poly_verify_batch`.

    Host side per row: the shape checks, the decode of C and π, and the
    row's coefficients as digit planes; no point arithmetic. Planes:
    cx/cy/c_inf (C), px/py/p_inf (π, the kernel's H slot, negated on
    the device), r_digits (bucket, terms, W) and z_digits
    (bucket, terms + 1, W), valid. `terms` is the set width: the widest
    well-shaped row's index count rounded up to a power of two, capped
    by the SRS's set cap. `msm_rows` counts the rows whose MSMs the device
    sums (every row past the host's checks)."""
    # lazy: scalar users of this module must never pull in jax
    from gethsharding_tpu.ops.bn256_jax import g1_to_limbs, msm_digits

    srs = pcs.dev_srs()
    rows = len(commitments)
    c_points = [None] * bucket
    p_points = [None] * bucket
    sets = [None] * bucket
    valid = np.zeros((bucket,), dtype=bool)
    widest = 1
    for b in range(rows):
        if not pcs.check_shape(index_rows[b], eval_rows[b], ns[b], srs):
            continue
        # the set width follows the rows' shapes, so a batch whose
        # points fail to decode keeps the shape of its honest twin
        widest = max(widest, len(index_rows[b]))
        try:
            c_points[b] = pcs.g1_from_bytes(commitments[b])
            p_points[b] = pcs.g1_from_bytes(proofs[b])
        except (TypeError, ValueError):
            c_points[b] = p_points[b] = None
            continue
        sets[b] = ([int(i) for i in index_rows[b]],
                   [int(e) for e in eval_rows[b]])
        valid[b] = True
    # Z sums terms + 1 G2 powers, and the SRS holds max_set + 1
    terms = min(1 << (widest - 1).bit_length(), srs.max_set)
    with tracing.stage("sig/poly_coeffs_time", _T_POLY_COEFFS):
        coeffs = [None if s is None else row_coeffs(*s) for s in sets]
        r_digits = msm_digits([None if c is None else c[0] for c in coeffs],
                              terms)
        z_digits = msm_digits([None if c is None else c[1] for c in coeffs],
                              terms + 1)
    cx, cy, c_ok = g1_to_limbs(c_points)
    px, py, p_ok = g1_to_limbs(p_points)
    return {"cx": cx, "cy": cy, "c_inf": ~c_ok, "px": px, "py": py,
            "p_inf": ~p_ok, "r_digits": r_digits, "z_digits": z_digits,
            "valid": valid, "rows": rows, "terms": terms,
            "msm_rows": int(valid.sum())}
