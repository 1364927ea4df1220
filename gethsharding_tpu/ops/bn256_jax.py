"""Batched bn256 (alt_bn128) ate pairing on TPU — the north-star kernel.

Re-architecture of the reference's hand-written pairing stack
(`crypto/bn256/cloudflare`: gfP Montgomery asm `gfp_amd64.s`, Miller loop
`optate.go`, `PairingCheck` `bn256.go:313`) as batch-first integer array
programs over the 12-bit-limb field engine (`ops/limb.py`):

- Fp2 = Fp[i]/(i²+1) as (..., 2, 22) int32.
- Fp12 in the FLAT w-basis: Fp12 = Fp2[w]/(w⁶ - ξ), ξ = 9+i, stored as
  (..., 6, 2, 22) — coefficient k of wᵏ is an Fp2 element. The nested
  2×3 tower (Fp6[w]/(w²-v)) is mathematically identical (w² = v) but the
  flat basis lets one einsum produce all 24 limb-product planes of a
  coefficient-pair convolution, and ONE batched normalize reduce all 12
  output components at once — an order of magnitude fewer graph nodes
  than per-component tower arithmetic (XLA:CPU segfaulted compiling the
  tower form of the batched pairing; this form compiles everywhere).
- Multiplication = length-6 cyclic convolution over the w axis with ξ on
  wrap-around, accumulated in raw schoolbook column space
  (`ModArith.mul_cols`) in groups of ≤4 products + pad (int32-safe).
- Miller loop: ate pairing, T = 6u² (trace-1) — the same loop the scalar
  reference `crypto/bn256.py` uses, so PairingCheck predicates agree by
  construction. G2 runs in Jacobian coordinates on the twist; line
  evaluations are inversion-free (each line is scaled by an Fp2 factor,
  which the final exponentiation kills). Static 127-bit `lax.scan`.
- Final exponentiation: easy part ((p⁶-1)(p²+1)) via conjugation + one
  tower inversion, then the standard hard-part addition chain
  (Devegili–Scott–Dahab) over f^u powers and Frobenius maps, run as a
  register-machine `lax.scan` so each fp12 primitive compiles once.
- On every platform but the CPU the projective Miller walk and the final
  exponentiation run as the two Pallas kernels of
  `ops/pallas_finalexp.py` (`pairing_in_pallas`); the XLA forms here
  are the CPU's and the mesh's.

Everything is shape-static, integer-only, and differential-tested against
the scalar `gethsharding_tpu.crypto.bn256` (tests/test_bn256_jax.py).
Batch axes are leading axes; `vmap`/`shard_map` compose.
"""

from __future__ import annotations

import os
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import bn256 as ref
from gethsharding_tpu.crypto import pointrows
from gethsharding_tpu.ops import limb as _limb
from gethsharding_tpu.ops.limb import ModArith, NLIMBS, ints_to_limbs, int_to_limbs

P = ref.P
N = ref.N
U = ref.U
FP = ModArith(P)

# Column-space bounds: one 25-limb product column < 25·4095² ≈ 2^28.64; an
# int32 column accumulator safely holds FOUR such products plus a canonical
# pad (< 2^12 per column): 4·2^28.64 + 2^12 < 2^30.7. Never sum more.
# Subtraction pads scale with the lazy VALUE bound (< 2^LAZY_BITS): a
# product of two lazy values is < 2^(2·273), so a sum of two subtracted
# products needs a multiple of p ≥ 2^547.
_PAD530 = FP.pad_mult(2 * _limb.LAZY_BITS + 1)  # ≥ two subtracted products

# GETHSHARDING_TPU_AGG=mega routes the masked committee tree reductions
# through the single-launch aggregation kernels (ops/pallas_finalexp.
# aggregate_proj) off the CPU — beside the pairing's two kernels
# (`pairing_in_pallas`) the audit dispatch is then 4 kernel launches
# (G1 agg, G2 agg, Miller, final exp).
AGG = os.environ.get("GETHSHARDING_TPU_AGG", "xla")
if AGG not in ("xla", "mega"):
    raise ValueError(f"GETHSHARDING_TPU_AGG must be 'xla' or 'mega', "
                     f"got {AGG!r}")


def _pair_conv_combine(x, y, comb: np.ndarray) -> jnp.ndarray:
    """cols[..., i, a, b, n] = sum_{l+m=n} x[i,a,l]·y[i,b,m], contracted
    against the static combine tensor -> (..., C, Gr, 2·NL-1) raw column
    accumulators: broadcast-multiply + conv_cols + einsum."""
    prod = x[..., :, :, None, :, None] * y[..., :, None, :, None, :]
    cols = _limb.conv_cols(prod)
    return jnp.einsum("...iabn,iabcg->...cgn", cols, jnp.asarray(comb))


def _pad_to(cols: jnp.ndarray, width: int) -> jnp.ndarray:
    return jnp.pad(cols, [(0, 0)] * (cols.ndim - 1) + [(0, width - cols.shape[-1])])


def _red(cols: jnp.ndarray) -> jnp.ndarray:
    return FP.normalize(cols)


def _red_sub(pos_cols: jnp.ndarray, neg_cols: jnp.ndarray) -> jnp.ndarray:
    """normalize(pos - neg + pad·p), pads aligned to a common width."""
    width = max(pos_cols.shape[-1], neg_cols.shape[-1], _PAD530.shape[0])
    z = _pad_to(pos_cols, width) - _pad_to(neg_cols, width)
    return FP.normalize(z + jnp.asarray(np.pad(_PAD530, (0, width - _PAD530.shape[0]))))


# == Fp2: (..., 2, 22), slot 0 = real, slot 1 = i-coefficient =============


def fp2_add(x, y):
    return FP.normalize(x + y)


def fp2_sub(x, y):
    return FP.sub(x, y)


def fp2_neg(x):
    return FP.neg(x)


# combine tensor for the (a+bi)(c+di) product planes: re = ac - bd,
# im = ad + bc
_COMB_FP2 = np.zeros((1, 2, 2, 2, 1), np.int32)
_COMB_FP2[0, 0, 0, 0, 0] = 1
_COMB_FP2[0, 1, 1, 0, 0] = -1
_COMB_FP2[0, 0, 1, 1, 0] = 1
_COMB_FP2[0, 1, 0, 1, 0] = 1

_FP2_W = max(2 * NLIMBS - 1, _PAD530.shape[0])
_FP2_PAD = np.zeros((2, _FP2_W), np.int32)  # pad only the subtracting re
_FP2_PAD[0, : _PAD530.shape[0]] = _PAD530


@jax.jit
def fp2_mul(x, y):
    """(a+bi)(c+di) = (ac - bd) + (ad + bc)i — fused, ONE normalize."""
    acc = _pair_conv_combine(x[..., None, :, :], y[..., None, :, :],
                             _COMB_FP2)[..., 0, :]  # (..., 2, ncols)
    return FP.normalize(_pad_to(acc, _FP2_W) + jnp.asarray(_FP2_PAD))


@jax.jit
def fp2_sqr(x):
    a, b = x[..., 0, :], x[..., 1, :]
    rr = _red_sub(FP.mul_cols(a, a), FP.mul_cols(b, b))
    ii = _red(FP.mul_cols(a, b) * 2)
    return jnp.stack([rr, ii], axis=-2)


def fp2_scalar(x, k: int):
    """Multiply both components by a small non-negative int."""
    return FP.mul_small(x, k)


def fp2_mul_fp(x, s):
    """Fp2 element times Fp element s (..., 22)."""
    a, b = x[..., 0, :], x[..., 1, :]
    return jnp.stack([FP.mul(a, s), FP.mul(b, s)], axis=-2)


_PAD266 = FP.pad_mult(_limb.LAZY_BITS)  # ≥ one lazy element (negated sums)


@jax.jit
def fp2_mul_xi(x):
    """×ξ = ×(9+i): (9a - b) + (a + 9b)i — 2 normalizes, no products."""
    a, b = x[..., 0, :], x[..., 1, :]
    width = max(a.shape[-1], _PAD266.shape[0])
    diff = _pad_to(a * 9 - b, width)
    rr = FP.normalize(diff + jnp.asarray(np.pad(
        _PAD266, (0, width - _PAD266.shape[0]))))
    ii = FP.normalize(a + b * 9)
    return jnp.stack([rr, ii], axis=-2)


def fp2_conj(x):
    a, b = x[..., 0, :], x[..., 1, :]
    return jnp.stack([FP.normalize(a), FP.neg(b)], axis=-2)


@jax.jit
def fp2_inv(x):
    """1/(a+bi) = (a - bi)/(a² + b²); inv(0) = 0."""
    a, b = x[..., 0, :], x[..., 1, :]
    norm = _red(FP.mul_cols(a, a) + FP.mul_cols(b, b))
    ninv = FP.inv(norm)
    return jnp.stack([FP.mul(a, ninv), FP.neg(FP.mul(b, ninv))], axis=-2)


def fp2_is_zero(x):
    return FP.is_zero(x[..., 0, :]) & FP.is_zero(x[..., 1, :])


def fp2_eq(x, y):
    return FP.eq(x[..., 0, :], y[..., 0, :]) & FP.eq(x[..., 1, :], y[..., 1, :])


def _const_fp2(value_a: int, value_b: int) -> np.ndarray:
    return np.stack([int_to_limbs(value_a % P), int_to_limbs(value_b % P)])


FP2_ZERO = np.zeros((2, NLIMBS), np.int32)
FP2_ONE = _const_fp2(1, 0)


# == Fp12 in the w-basis: (..., 6, 2, 22), w⁶ = ξ =========================

FP12_ONE = np.zeros((6, 2, NLIMBS), np.int32)
FP12_ONE[0, 0, 0] = 1

# static index tables for the cyclic convolution: output k takes, for each
# i, operand j = (k - i) mod 6 — from y when i + j == k, from ξ·y on wrap
_CONV_J = np.array([[(k - i) % 6 for i in range(6)] for k in range(6)])
_CONV_SEL = np.array([[0 if i + (k - i) % 6 == k else 1 for i in range(6)]
                      for k in range(6)])

# combine tensor per output k: map the 24 limb-product planes (i, a, b) to
# output component c ∈ {re, im} and accumulation group g = i // 2 (so each
# group holds 2 pairs = ≤4 products): re += (a0b0) - (a1b1); im += a0b1 + a1b0
_COMB = np.zeros((6, 2, 2, 2, 3), np.int32)  # (i, a, b, c, g)
for _i in range(6):
    _g = _i // 2
    _COMB[_i, 0, 0, 0, _g] = 1
    _COMB[_i, 1, 1, 0, _g] = -1
    _COMB[_i, 0, 1, 1, _g] = 1
    _COMB[_i, 1, 0, 1, _g] = 1

# per-group pad: real groups subtract ≤2 products (< 2^529) — pad with a
# multiple of p ≥ 2^530; imag groups are all-positive, no pad needed.
# Accumulator width = max(product columns, pad limbs).
_ACC_W = max(2 * NLIMBS - 1, _PAD530.shape[0])


def _group_pad(n_groups: int) -> np.ndarray:
    pad = np.zeros((2, n_groups, _ACC_W), np.int32)
    pad[0, :, : _PAD530.shape[0]] = _PAD530
    return pad


@jax.jit
def fp12_mul(x, y):
    """w-basis product: cyclic convolution with ξ wrap-around.

    Per output k: one einsum builds the 24 limb-product column planes of
    the 6 contributing (xᵢ, opⱼ) Fp2 pairs, one einsum folds them into
    (component, group) accumulators; a single batched normalize then
    reduces all (k, c, g) at once, and a 2-level tree of batched lazy adds
    merges the 3 groups."""
    xiy = fp2_mul_xi(y)                      # (..., 6, 2, 22), ξ·y_j
    w = jnp.stack([y, xiy], axis=-4)         # (..., 2sel, 6, 2, 22)
    pad = jnp.asarray(_group_pad(3))

    group_cols = []
    for k in range(6):
        op = w[..., _CONV_SEL[k], _CONV_J[k], :, :]   # (..., 6, 2, 22)
        # cols[..., i, a, b, n] = sum_{l+m=n} x[i,a,l]·op[i,b,m], folded
        # into (component, group) accumulators; plus pads
        acc = _pad_to(_pair_conv_combine(x, op, _COMB), _ACC_W) + pad
        group_cols.append(acc)
    acc = jnp.stack(group_cols, axis=-4)     # (..., 6, 2, 3, width)
    parts = FP.normalize(acc)                # (..., 6, 2, 3, 22)
    merged = FP.normalize(parts[..., 0, :] + parts[..., 1, :])
    return FP.normalize(merged + parts[..., 2, :])


@jax.jit
def fp12_sqr(x):
    return fp12_mul(x, x)


@jax.jit
def fp12_conj(x):
    """f^(p⁶): negate the odd-w coefficients (w^(p⁶) = -w)."""
    neg = FP.neg(x)
    odd = jnp.asarray(
        np.arange(6).reshape(6, 1, 1) % 2 == 1)
    return jnp.where(odd, neg, FP.normalize(x))


def _h6(x, parity):
    """Tower slice: even w-coeffs = Fp6 c0, odd = c1 (since w² = v)."""
    return x[..., parity::2, :, :]


def _interleave6(lo, hi):
    """(..., 3, 2, 22) × 2 -> (..., 6, 2, 22), w-coeff k = (k%2 ? hi : lo)[k//2]."""
    stacked = jnp.stack([lo, hi], axis=-3)   # (..., 3, 2par, 2, 22)
    return stacked.reshape(stacked.shape[:-4] + (6,) + stacked.shape[-2:])


# -- Fp6 helpers on tower slices (used by inversion only) -----------------


def _c(x, k):
    return x[..., k, :, :]


def fp6_add(x, y):
    return FP.normalize(x + y)


def fp6_sub(x, y):
    return FP.sub(x, y)


def fp6_neg(x):
    return FP.neg(x)


@jax.jit
def fp6_mul(x, y):
    """Schoolbook with v³ = ξ (mirrors scalar Fp6.__mul__)."""
    a0, a1, a2 = _c(x, 0), _c(x, 1), _c(x, 2)
    b0, b1, b2 = _c(y, 0), _c(y, 1), _c(y, 2)
    t0 = fp2_mul(a0, b0)
    t1 = fp2_add(fp2_mul(a0, b1), fp2_mul(a1, b0))
    t2 = fp2_add(fp2_add(fp2_mul(a0, b2), fp2_mul(a1, b1)), fp2_mul(a2, b0))
    t3 = fp2_add(fp2_mul(a1, b2), fp2_mul(a2, b1))  # v³ -> ξ
    t4 = fp2_mul(a2, b2)  # v⁴ -> ξ·v
    return jnp.stack(
        [fp2_add(t0, fp2_mul_xi(t3)), fp2_add(t1, fp2_mul_xi(t4)), t2], axis=-3)


def fp6_mul_by_v(x):
    """(c0, c1, c2) -> (ξ·c2, c0, c1)."""
    return jnp.stack([fp2_mul_xi(_c(x, 2)), _c(x, 0), _c(x, 1)], axis=-3)


@jax.jit
def fp6_inv(x):
    """Cubic-extension inversion via the adjoint matrix (scalar parity)."""
    a, b, c = _c(x, 0), _c(x, 1), _c(x, 2)
    t0 = fp2_sub(fp2_sqr(a), fp2_mul_xi(fp2_mul(b, c)))
    t1 = fp2_sub(fp2_mul_xi(fp2_sqr(c)), fp2_mul(a, b))
    t2 = fp2_sub(fp2_sqr(b), fp2_mul(a, c))
    denom = fp2_add(fp2_mul(a, t0),
                    fp2_mul_xi(fp2_add(fp2_mul(c, t1), fp2_mul(b, t2))))
    dinv = fp2_inv(denom)
    return jnp.stack(
        [fp2_mul(t0, dinv), fp2_mul(t1, dinv), fp2_mul(t2, dinv)], axis=-3)


@jax.jit
def fp12_inv(x):
    """(c0 + c1 w)⁻¹ via the quadratic norm over the Fp6 tower slices."""
    c0, c1 = _h6(x, 0), _h6(x, 1)
    denom = fp6_sub(fp6_mul(c0, c0), fp6_mul_by_v(fp6_mul(c1, c1)))
    dinv = fp6_inv(denom)
    return _interleave6(fp6_mul(c0, dinv), fp6_neg(fp6_mul(c1, dinv)))


def fp12_select(cond, x, y):
    return jnp.where(cond[..., None, None, None], x, y)


def fp12_is_one(x):
    one = jnp.asarray(FP12_ONE)
    return jnp.all(
        FP.canon(x) == FP.canon(jnp.broadcast_to(one, x.shape)),
        axis=(-1, -2, -3))


# == Frobenius maps =======================================================
# (a·wᵏ)^(pⁿ) = conjⁿ(a) · γ_{n,k} · wᵏ with γ_{n,k} = ξ^(k(pⁿ-1)/6) ∈ Fp2.


def _gamma_table(n: int) -> np.ndarray:
    """(6, 2, 22) limb constants γ_{n,k} for k = 0..5."""
    rows = []
    for k in range(6):
        g = ref._fp2_pow(ref.XI, k * (P**n - 1) // 6)
        rows.append(_const_fp2(g.a, g.b))
    return np.stack(rows)


_GAMMA = {n: _gamma_table(n) for n in (1, 2, 3)}


def fp12_frobenius(x, n: int):
    """f^(pⁿ) for n ∈ {1, 2, 3} — batched over all six w-coefficients."""
    coeff = fp2_conj(x) if n % 2 == 1 else FP.normalize(x)
    return fp2_mul(coeff, jnp.asarray(_GAMMA[n]))


# == G2 Jacobian steps with line evaluation ================================
# Twist point T = (X, Y, Z) Jacobian (x = X/Z², y = Y/Z³), each Fp2.
# Lines are evaluated at P = (px, py) ∈ G1 and scaled by an Fp2 factor
# (killed by the final exponentiation). Sparse form: ℓ = A + B·w + C·w³
# with A = c_py·py, B = c_px·px, C = c_const, all Fp2.


def _dbl_coeffs(X, Y, Z):
    """Tangent step, coefficient form: ((c_py, c_px, c_const), X3, Y3,
    Z3) with the line ℓ = c_py·y + c_px·x + c_const left UNevaluated —
    the fixed-base precompute path stores the three Fp2 coefficients
    and evaluates them against a fresh G1 argument per dispatch."""
    A = fp2_sqr(X)
    B = fp2_sqr(Y)
    C = fp2_sqr(B)
    t = fp2_sqr(fp2_add(X, B))
    D = fp2_scalar(fp2_sub(fp2_sub(t, A), C), 2)  # 4XY²
    E = fp2_scalar(A, 3)
    F = fp2_sqr(E)
    X3 = fp2_sub(F, fp2_scalar(D, 2))
    Y3 = fp2_sub(fp2_mul(E, fp2_sub(D, X3)), fp2_scalar(C, 8))
    ZZ = fp2_sqr(Z)
    Z3 = fp2_scalar(fp2_mul(Y, Z), 2)
    c_py = fp2_mul(Z3, ZZ)                       # 2YZ³
    c_px = fp2_neg(fp2_mul(E, ZZ))               # -3X²Z²
    c_const = fp2_sub(fp2_mul(E, X), fp2_scalar(B, 2))  # 3X³ - 2Y²
    return (c_py, c_px, c_const), X3, Y3, Z3


def _dbl_step(X, Y, Z, px, py):
    """Tangent step: returns (line (A,B,C), X3, Y3, Z3). Scale = 2YZ³."""
    (c_py, c_px, c_const), X3, Y3, Z3 = _dbl_coeffs(X, Y, Z)
    line = (fp2_mul_fp(c_py, py), fp2_mul_fp(c_px, px), c_const)
    return line, X3, Y3, Z3


def _madd_step(X1, Y1, Z1, x2, y2, px, py):
    """Chord step vs affine Q = (x2, y2): line scale = Z3 = Z1·H."""
    Z1Z1 = fp2_sqr(Z1)
    U2 = fp2_mul(x2, Z1Z1)
    S2 = fp2_mul(y2, fp2_mul(Z1, Z1Z1))
    H = fp2_sub(U2, X1)
    R = fp2_sub(S2, Y1)
    HH = fp2_sqr(H)
    V = fp2_mul(X1, HH)
    HHH = fp2_mul(H, HH)
    X3 = fp2_sub(fp2_sub(fp2_sqr(R), HHH), fp2_scalar(V, 2))
    Y3 = fp2_sub(fp2_mul(R, fp2_sub(V, X3)), fp2_mul(Y1, HHH))
    Z3 = fp2_mul(Z1, H)
    c_const = fp2_sub(fp2_mul(R, x2), fp2_mul(Z3, y2))
    line = (fp2_mul_fp(Z3, py), fp2_mul_fp(fp2_neg(R), px), c_const)
    return line, X3, Y3, Z3


# sparse line-mul tables: ℓ = A·w⁰ + B·w¹ + C·w³; output k takes
# A·f_k, B·f_{k-1} (ξ·f_{k+5} on wrap), C·f_{k-3} (ξ·f_{k+3} on wrap)
_LINE_POS = np.array([0, 1, 3])  # w-degrees of A, B, C
_LINE_J = np.array([[(k - d) % 6 for d in _LINE_POS] for k in range(6)])
_LINE_SEL = np.array([[0 if k - d >= 0 else 1 for d in _LINE_POS]
                      for k in range(6)])
# combine: (t∈3 line terms, a, b, c, g): group 0 = terms A,B; group 1 = C
_LCOMB = np.zeros((3, 2, 2, 2, 2), np.int32)
for _t in range(3):
    _g = 0 if _t < 2 else 1
    _LCOMB[_t, 0, 0, 0, _g] = 1
    _LCOMB[_t, 1, 1, 0, _g] = -1
    _LCOMB[_t, 0, 1, 1, _g] = 1
    _LCOMB[_t, 1, 0, 1, _g] = 1


@jax.jit
def fp12_mul_line(f, line):
    """f · (A + B·w + C·w³) — sparse convolution, same fusion scheme."""
    A, B, C = line
    lstack = jnp.stack([A, B, C], axis=-3)   # (..., 3, 2, 22)
    xif = fp2_mul_xi(f)
    w = jnp.stack([f, xif], axis=-4)         # (..., 2sel, 6, 2, 22)
    pad = jnp.asarray(_group_pad(2))

    group_cols = []
    for k in range(6):
        op = w[..., _LINE_SEL[k], _LINE_J[k], :, :]   # (..., 3, 2, 22)
        acc = _pad_to(_pair_conv_combine(lstack, op, _LCOMB),
                      _ACC_W) + pad
        group_cols.append(acc)
    acc = jnp.stack(group_cols, axis=-4)     # (..., 6, 2, 2, width)
    parts = FP.normalize(acc)
    return FP.normalize(parts[..., 0, :] + parts[..., 1, :])


# == Miller loop (ate, T = 6u²) ===========================================

ATE_BITS = np.array(
    [int(b) for b in bin(ref.ATE_LOOP_COUNT)[3:]], np.int32)  # MSB consumed


def miller_loop(px, py, qx, qy):
    """f_{T,Q}(P) batched. px/py (..., 22); qx/qy (..., 2, 22) affine G2.

    Inputs must be valid curve points; infinity handling is the caller's
    (mask + select, see pairing_check)."""
    shape = px.shape[:-1]
    # zero derived from a varying input so constant-built scan carries
    # inherit the varying manual axes under shard_map
    vzero = (px[..., :1] * 0)[..., None]  # (..., 1, 1)
    f = jnp.broadcast_to(jnp.asarray(FP12_ONE),
                         shape + (6, 2, NLIMBS)) + vzero[..., None]
    X = jnp.broadcast_to(qx, shape + (2, NLIMBS))
    Y = jnp.broadcast_to(qy, shape + (2, NLIMBS))
    Z = jnp.broadcast_to(jnp.asarray(FP2_ONE), shape + (2, NLIMBS)) + vzero
    # normalize broadcasts into concrete arrays for scan carry stability
    f, X, Y, Z = map(FP.normalize, (f, X, Y, Z))

    def step(carry, bit):
        f, X, Y, Z = carry
        line, X, Y, Z = _dbl_step(X, Y, Z, px, py)
        f = fp12_mul_line(fp12_sqr(f), line)
        line_a, Xa, Ya, Za = _madd_step(X, Y, Z, qx, qy, px, py)
        fa = fp12_mul_line(f, line_a)
        take = jnp.broadcast_to(bit == 1, shape)
        f = fp12_select(take, fa, f)
        sel = lambda a, b: jnp.where(take[..., None, None], a, b)
        return (f, sel(Xa, X), sel(Ya, Y), sel(Za, Z)), None

    (f, X, Y, Z), _ = lax.scan(step, (f, X, Y, Z), jnp.asarray(ATE_BITS))
    return f


# == Final exponentiation ==================================================

# The hard part runs as a small register machine under ONE lax.scan so XLA
# compiles each fp12 primitive once (an inline chain of ~25 fp12_muls
# multiplies compile time by the chain length). Ops: 0 mul, 1 sqr, 2 conj,
# 3/4/5 frobenius¹/²/³. Registers: 14 × Fp12.
# Program = the Devegili–Scott–Dahab chain; register plan in comments.
_HARD_PROGRAM = np.array([
    # (op, src_a, src_b, dst) — registers 1..3 (f^u, f^u², f^u³) are filled
    # by plain _pow_u calls before the scan; XLA dedups their identical
    # inner scans, and the switch branches stay light.
    (3, 0, 0, 4),    # r4 = frob1(f)
    (4, 0, 0, 5),    # r5 = frob2(f)
    (5, 0, 0, 6),    # r6 = frob3(f)
    (0, 4, 5, 4),    # r4 = r4·r5
    (0, 4, 6, 4),    # y0 = r4 = r4·r6
    (2, 0, 0, 5),    # y1 = r5 = conj(f)
    (4, 2, 0, 6),    # y2 = r6 = frob2(fu2)
    (3, 1, 0, 7),    # r7 = frob1(fu)
    (2, 7, 0, 7),    # y3 = r7 = conj(r7)
    (3, 2, 0, 8),    # r8 = frob1(fu2)
    (0, 1, 8, 8),    # r8 = fu·r8
    (2, 8, 0, 8),    # y4 = r8 = conj(r8)
    (2, 2, 0, 9),    # y5 = r9 = conj(fu2)
    (3, 3, 0, 10),   # r10 = frob1(fu3)
    (0, 3, 10, 10),  # r10 = fu3·r10
    (2, 10, 0, 10),  # y6 = r10 = conj(r10)
    (1, 10, 0, 11),  # t0 = r11 = y6²
    (0, 11, 8, 11),  # t0 = t0·y4
    (0, 11, 9, 11),  # t0 = t0·y5
    (0, 7, 9, 12),   # t1 = r12 = y3·y5
    (0, 12, 11, 12),  # t1 = t1·t0
    (0, 11, 6, 11),  # t0 = t0·y2
    (1, 12, 0, 12),  # t1 = t1²
    (0, 12, 11, 12),  # t1 = t1·t0
    (1, 12, 0, 12),  # t1 = t1²
    (0, 12, 5, 13),  # t0' = r13 = t1·y1
    (0, 12, 4, 12),  # t1 = t1·y0
    (1, 13, 0, 13),  # t0' = t0'²
    (0, 13, 12, 13),  # result = r13 = t0'·t1
], np.int32)
_N_REGS = 14

_U_BITS = np.array([(U >> i) & 1 for i in range(U.bit_length())], np.int32)
_U_NAF = np.asarray(ref._naf(U), np.int32)  # little-endian digits of u


def _pow_u(x):
    """x^u (u = BN parameter, 63 static bits) via square-multiply scan."""
    def step(carry, bit):
        acc, base = carry
        take = jnp.broadcast_to(bit == 1, acc.shape[:-3])
        acc = fp12_select(take, fp12_mul(acc, base), acc)
        return (acc, fp12_sqr(base)), None

    acc0 = FP.normalize(
        jnp.broadcast_to(jnp.asarray(FP12_ONE), x.shape) + x * 0)
    (acc, _), _ = lax.scan(step, (acc0, x), jnp.asarray(_U_BITS))
    return acc


def _run_hard_part(f, pow_u_fn, inv_fn):
    """The DSD hard-part register machine (see _HARD_PROGRAM), shared by
    the value path (inverse = cyclotomic conjugate) and the fraction path
    (inverse = component swap)."""
    regs = jnp.broadcast_to(
        jnp.asarray(FP12_ONE), (_N_REGS,) + f.shape).astype(jnp.int32) + f * 0
    regs = FP.normalize(regs)
    regs = regs.at[0].set(f)
    fu = pow_u_fn(f)
    fu2 = pow_u_fn(fu)
    regs = regs.at[1].set(fu)
    regs = regs.at[2].set(fu2)
    regs = regs.at[3].set(pow_u_fn(fu2))

    def step(regs, instr):
        op, a, b, d = instr[0], instr[1], instr[2], instr[3]
        ra = lax.dynamic_index_in_dim(regs, a, axis=0, keepdims=False)
        rb = lax.dynamic_index_in_dim(regs, b, axis=0, keepdims=False)
        out = lax.switch(op, [
            lambda ra, rb: fp12_mul(ra, rb),
            lambda ra, rb: fp12_sqr(ra),
            lambda ra, rb: inv_fn(ra),
            lambda ra, rb: fp12_frobenius(ra, 1),
            lambda ra, rb: fp12_frobenius(ra, 2),
            lambda ra, rb: fp12_frobenius(ra, 3),
        ], ra, rb)
        return lax.dynamic_update_index_in_dim(regs, out, d, axis=0), None

    regs, _ = lax.scan(step, regs, jnp.asarray(_HARD_PROGRAM))
    return regs[13]


def final_exponentiation(f):
    """f^((p¹²-1)/n): easy part then the DSD hard-part addition chain."""
    # easy: f^(p⁶-1), then ^(p²+1)
    f = fp12_mul(fp12_conj(f), fp12_inv(f))
    f = fp12_mul(fp12_frobenius(f, 2), f)
    return _run_hard_part(f, _pow_u, fp12_conj)


# == Inversion-free pairing check ==========================================
# The boolean check is_one(f^((p¹²-1)/n)) never needs a field inversion:
# f^(p⁶-1) = conj(f)/f is carried as a STACKED FRACTION (leading axis 2 =
# numerator/denominator). Every hard-part op is a group homomorphism
# (mul/sqr/frobenius apply componentwise, batched over the fraction axis),
# and the DSD chain's "conjugate = cyclotomic inverse" becomes a free
# component swap — valid on fractions of arbitrary elements, since for the
# represented (cyclotomic) quotient swap(N,D) represents exactly (N/D)⁻¹.
# The final is_one collapses to canon(N) == canon(D). This removes the
# ~254-squaring Fermat inversion from the hot path, the single deepest
# sequential chain in the r1 kernel.


def _pow_u_fraction(x):
    """x^u on a fraction-stacked element (leading axis 2 = num/den).

    NAF digits of u (static): digit 0 costs one squaring; ±1 digits one
    extra mul, with -1 multiplying by the SWAPPED fraction (free inverse).
    """
    xswap = x[::-1]
    digits = list(reversed(_U_NAF[:-1]))

    def step(acc, d):
        acc = fp12_sqr(acc)
        acc = lax.switch(d + 1, [
            lambda a: fp12_mul(a, xswap),
            lambda a: a,
            lambda a: fp12_mul(a, x),
        ], acc)
        return acc, None

    acc, _ = lax.scan(step, x,
                      jnp.asarray(np.asarray(digits, np.int32)))
    return acc


def fp12_eq(x, y):
    return jnp.all(FP.canon(x) == FP.canon(y), axis=(-1, -2, -3))


def pairing_in_pallas(pallas=None) -> bool:
    """Does a pairing check run in the Pallas kernels of
    ops/pallas_finalexp.py (`miller_f`, `finalexp_is_one`: each a
    register machine in ONE launch, where XLA runs ~90 Miller steps and
    ~250 fp12 operations as chains of fusions)? `pallas` is the caller's
    trace-time choice: None takes the platform's choice (every platform
    but the CPU, `limb._pallas_wanted`), False the XLA path, which a
    mesh step passes because a `pallas_call` inside `shard_map` fails at
    trace."""
    return _limb._pallas_wanted() if pallas is None else bool(pallas)


def pairing_is_one(f, pallas=None):
    """is_one(final_exponentiation(f)) without any field inversion;
    `pallas` as in `pairing_in_pallas`."""
    if pairing_in_pallas(pallas):
        from gethsharding_tpu.ops.pallas_finalexp import finalexp_is_one

        return finalexp_is_one(f)
    nd = jnp.stack([fp12_conj(f), FP.normalize(f)])  # conj(f)/f = f^(p⁶-1)
    nd = fp12_mul(fp12_frobenius(nd, 2), nd)         # ^(p²+1)
    nd = _run_hard_part(nd, _pow_u_fraction, lambda ra: ra[::-1])
    return fp12_eq(nd[0], nd[1])


# == Pairing check / BLS batch verification ================================


def pairing_product(px, py, qx, qy, mask):
    """∏ over the last batch axis of Miller loops, masked pairs -> 1.

    px/py: (..., K, 22); qx/qy: (..., K, 2, 22); mask: (..., K) bool.
    Returns the K-product BEFORE final exponentiation.
    """
    f = miller_loop(px, py, qx, qy)  # (..., K, 6, 2, 22)
    one = jnp.broadcast_to(jnp.asarray(FP12_ONE), f.shape)
    f = fp12_select(mask, f, one)
    k = f.shape[-4]
    acc = f[..., 0, :, :, :]
    for j in range(1, k):  # K is small (2 for BLS verify)
        acc = fp12_mul(acc, f[..., j, :, :, :])
    return acc


def pairing_check(px, py, qx, qy, mask):
    """Batched PairingCheck: ∏ e(Pᵢ, Qᵢ) == 1 per leading-batch element.

    Boolean parity with `bn256.PairingCheck` (cloudflare/bn256.go:313);
    fraction axis is prepended INSIDE pairing_is_one, so any leading batch
    shape composes.
    """
    return pairing_is_one(pairing_product(px, py, qx, qy, mask))


# == Optimal-ate Miller loop with a shared accumulator =====================
# The BLS hot loop checks e(sig, G2_GEN)·e(-H, pk) == 1. Three structural
# wins over running `miller_loop` per pair (scalar twin:
# `crypto/bn256.py miller_loop_optimal`; reference analog: the optimal-ate
# loop of `crypto/bn256/cloudflare/optate.go`):
# - loop count 6u+2 (66-digit NAF, weight 22) instead of 6u² (127 bits):
#   88 program steps vs 127, plus two Frobenius adjustment lines;
# - ONE shared f accumulator: per doubling step a single fp12_sqr serves
#   both pairs (the product ∏fᵢ is accumulated in-loop);
# - the generator pairing's line COEFFICIENTS are precomputed on the host
#   as numpy constants (the G2 walk doesn't depend on runtime data), so
#   pair 0 contributes two fp2-by-scalar products per step instead of a
#   full Jacobian double/add chain.

def _host_jac_dbl(X, Y, Z):
    """Host twin of _dbl_step on ref.Fp2 (same formulas, same scales)."""
    A = X * X
    B = Y * Y
    C = B * B
    t = (X + B) * (X + B)
    D = (t - A - C).scalar(2)
    E = A.scalar(3)
    F = E * E
    X3 = F - D.scalar(2)
    Y3 = E * (D - X3) - C.scalar(8)
    ZZ = Z * Z
    Z3 = (Y * Z).scalar(2)
    line = (Z3 * ZZ, (E * ZZ).neg(), E * X - B.scalar(2))
    return line, X3, Y3, Z3


def _host_jac_madd(X1, Y1, Z1, x2, y2):
    """Host twin of _madd_step on ref.Fp2."""
    Z1Z1 = Z1 * Z1
    U2 = x2 * Z1Z1
    S2 = y2 * Z1 * Z1Z1
    H = U2 - X1
    R = S2 - Y1
    HH = H * H
    V = X1 * HH
    HHH = H * HH
    X3 = R * R - HHH - V.scalar(2)
    Y3 = R * (V - X3) - Y1 * HHH
    Z3 = Z1 * H
    line = (Z3, R.neg(), R * x2 - Z3 * y2)
    return line, X3, Y3, Z3


def _build_opt_program():
    """(ops, gen_lines): the static optimal-ate schedule and the
    precomputed G2-generator line coefficients along it.

    ops (L,) int32: 0 = DBL, 1 = ADD(+Q), 2 = ADD(-Q), 3 = ADD(πQ),
    4 = ADD(-π²Q). gen_lines (L, 3, 2, 22): (c_py, c_px, c_const) per step.
    """
    ops = []
    for d in reversed(ref.OPT_ATE_NAF[:-1]):
        ops.append(0)
        if d == 1:
            ops.append(1)
        elif d == -1:
            ops.append(2)
    ops += [3, 4]

    q = ref.G2_GEN
    cands = [q, ref.g2_neg(q), ref.g2_frobenius(q),
             ref.g2_neg(ref.g2_frobenius2(q))]
    (X, Y), Z = q, ref.Fp2.one()
    lines = []
    for op in ops:
        if op == 0:
            line, X, Y, Z = _host_jac_dbl(X, Y, Z)
        else:
            x2, y2 = cands[op - 1]
            line, X, Y, Z = _host_jac_madd(X, Y, Z, x2, y2)
        lines.append(np.stack([_const_fp2(c.a, c.b) for c in line]))
    return np.asarray(ops, np.int32), np.stack(lines)


_OPT_OPS, _GEN_LINES = _build_opt_program()
_TWF_X = _const_fp2(ref.TWIST_FROB_X.a, ref.TWIST_FROB_X.b)
_TWF_Y = _const_fp2(ref.TWIST_FROB_Y.a, ref.TWIST_FROB_Y.b)
_TWF2_X = _const_fp2(ref.TWIST_FROB2_X.a, ref.TWIST_FROB2_X.b)
_TWF2_Y = _const_fp2(ref.TWIST_FROB2_Y.a, ref.TWIST_FROB2_Y.b)


def _jadd_coeffs(X1, Y1, Z1, cand):
    """Full Jacobian + Jacobian chord step, coefficient form: returns
    ((c_py, c_px, c_const), X3, Y3, Z3) with the chord line left
    UNevaluated (c_py = Z3, c_px = −R) so the fixed-base precompute
    path can store the three Fp2 coefficients per schedule step."""
    x2, y2, z2, zz2, zzz2 = cand
    Z1Z1 = fp2_sqr(Z1)
    U1 = fp2_mul(X1, zz2)
    U2 = fp2_mul(x2, Z1Z1)
    S1 = fp2_mul(Y1, zzz2)
    S2 = fp2_mul(y2, fp2_mul(Z1, Z1Z1))
    H = fp2_sub(U2, U1)
    R = fp2_sub(S2, S1)
    HH = fp2_sqr(H)
    V = fp2_mul(U1, HH)
    HHH = fp2_mul(H, HH)
    X3 = fp2_sub(fp2_sub(fp2_sqr(R), HHH), fp2_scalar(V, 2))
    Y3 = fp2_sub(fp2_mul(R, fp2_sub(V, X3)), fp2_mul(S1, HHH))
    Z3 = fp2_mul(fp2_mul(Z1, z2), H)
    c_const = fp2_sub(fp2_mul(fp2_mul(X1, y2), Z1),
                      fp2_mul(fp2_mul(x2, Y1), z2))
    return (Z3, fp2_neg(R), c_const), X3, Y3, Z3


def _jadd_step(X1, Y1, Z1, cand, px, py):
    """Full Jacobian + Jacobian chord step against candidate Q₂ (its
    per-shard constants precomputed: X2, Y2, Z2, Z2², Z2³).

    Line ℓ·(Z1Z2)³ = py·Z3 − px·R + (X1Y2Z1 − X2Y1Z2) — the true chord
    through T and Q₂ up to an Fp2 scale (killed by the final
    exponentiation), reducing to `_madd_step`'s line when Z2 = 1."""
    (c_py, c_px, c_const), X3, Y3, Z3 = _jadd_coeffs(X1, Y1, Z1, cand)
    line = (fp2_mul_fp(c_py, py), fp2_mul_fp(c_px, px), c_const)
    return line, X3, Y3, Z3


def _bls_miller_opt(sig, hx, hy, pk, pallas=None):
    """Shared-accumulator optimal-ate Miller product for the BLS check.

    Pair 0: (sig, G2_GEN) via precomputed static lines evaluated at sig.
    Pair 1: (-H, pk) via a dynamic Jacobian walk on the twist.
    Returns f = miller(sig, G2)·miller(-H, pk) before final exponentiation.

    `sig` = (sx, sy, sz) PROJECTIVE G1 limbs and `pk` = (pkx, pky, pkz)
    projective G2 limbs — the on-device aggregation outputs, consumed
    without any field inversion: pair 0's lines absorb sz as an Fp scale,
    and pk enters the walk through the Jacobian lift (X·Z, Y·Z², Z) with
    full-Jacobian chord steps. Every extra scale lives in Fp2* and dies
    in the final exponentiation. Affine callers pass z = None — a
    TRACE-TIME specialization that keeps the cheaper mixed-addition
    steps and constant generator-line terms of the affine form. The
    projective walk runs in `pallas_finalexp.miller_f` wherever
    `pairing_in_pallas(pallas)`; the affine one always here.
    """
    sx, sy, sz = sig
    pkx, pky, pkz = pk
    affine = pkz is None
    if not affine and sz is not None and pairing_in_pallas(pallas):
        from gethsharding_tpu.ops.pallas_finalexp import miller_f

        return miller_f(sig, hx, hy, pk)
    shape = sx.shape[:-1]
    hy_neg = FP.neg(hy)

    # dynamic add candidates [+Q, -Q, πQ, -π²Q] for Q = pk: affine pairs,
    # or Jacobian lifts of the projective candidates (Xc·Zc, Yc·Zc², Zc)
    # with their Z2 powers precomputed once per shard
    q1x = fp2_mul(fp2_conj(pkx), jnp.asarray(_TWF_X))
    q1y = fp2_mul(fp2_conj(pky), jnp.asarray(_TWF_Y))
    q2x = fp2_mul(pkx, jnp.asarray(_TWF2_X))
    q2ny = FP.neg(fp2_mul(pky, jnp.asarray(_TWF2_Y)))
    proj_x = [pkx, pkx, q1x, q2x]
    proj_y = [pky, FP.neg(pky), q1y, q2ny]
    if affine:
        cand = (jnp.stack(proj_x), jnp.stack(proj_y))
    else:
        zconj = fp2_conj(pkz)
        proj_z = [pkz, pkz, zconj, pkz]
        jac = []
        for cx, cy, cz in zip(proj_x, proj_y, proj_z):
            zz = fp2_sqr(cz)
            jac.append((fp2_mul(cx, cz), fp2_mul(cy, zz), cz, zz,
                        fp2_mul(cz, zz)))
        cand = tuple(jnp.stack([j[k] for j in jac]) for k in range(5))

    vzero = (sx[..., :1] * 0)[..., None]           # (..., 1, 1)
    f = FP.normalize(jnp.broadcast_to(jnp.asarray(FP12_ONE),
                                      shape + (6, 2, NLIMBS)) + vzero[..., None])
    if affine:
        X = FP.normalize(jnp.broadcast_to(pkx, shape + (2, NLIMBS)))
        Y = FP.normalize(jnp.broadcast_to(pky, shape + (2, NLIMBS)))
        Z = FP.normalize(jnp.broadcast_to(jnp.asarray(FP2_ONE),
                                          shape + (2, NLIMBS)) + vzero)
    else:
        # walk start T = Q as the Jacobian lift of projective pk
        X = fp2_mul(pkx, pkz)
        Y = fp2_mul(pky, fp2_sqr(pkz))
        Z = FP.normalize(jnp.broadcast_to(pkz, shape + (2, NLIMBS)))

    def gen_line(line_c):
        """Static generator line evaluated at P0 = sig:
        (c_py·y + c_px·x + c_const)·z — sz scales the constant term
        (skipped when sig is affine: z = 1)."""
        A = fp2_mul_fp(line_c[0], sy)
        B = fp2_mul_fp(line_c[1], sx)
        C = jnp.broadcast_to(FP.normalize(line_c[2]), shape + (2, NLIMBS))
        if sz is not None:
            C = fp2_mul_fp(C, sz)
        return A, B, C

    def dbl_branch(f, X, Y, Z, line_c, op):
        line1, X, Y, Z = _dbl_step(X, Y, Z, hx, hy_neg)
        f = fp12_sqr(f)
        f = fp12_mul_line(f, gen_line(line_c))
        f = fp12_mul_line(f, line1)
        return f, X, Y, Z

    def add_branch(f, X, Y, Z, line_c, op):
        idx = op - 1
        if affine:
            x2 = lax.dynamic_index_in_dim(cand[0], idx, axis=0,
                                          keepdims=False)
            y2 = lax.dynamic_index_in_dim(cand[1], idx, axis=0,
                                          keepdims=False)
            line1, X, Y, Z = _madd_step(X, Y, Z, x2, y2, hx, hy_neg)
        else:
            q2 = tuple(
                lax.dynamic_index_in_dim(c, idx, axis=0, keepdims=False)
                for c in cand)
            line1, X, Y, Z = _jadd_step(X, Y, Z, q2, hx, hy_neg)
        f = fp12_mul_line(f, gen_line(line_c))
        f = fp12_mul_line(f, line1)
        return f, X, Y, Z

    def step(carry, xs):
        op, line_c = xs
        f, X, Y, Z = carry
        f, X, Y, Z = lax.cond(op == 0, dbl_branch, add_branch,
                              f, X, Y, Z, line_c, op)
        return (f, X, Y, Z), None

    (f, X, Y, Z), _ = lax.scan(
        step, (f, X, Y, Z),
        (jnp.asarray(_OPT_OPS), jnp.asarray(_GEN_LINES)))
    return f


# generator / BLS fixed points as limb constants
G2_GEN_X = np.stack([int_to_limbs(ref.G2_GEN[0].a), int_to_limbs(ref.G2_GEN[0].b)])
G2_GEN_Y = np.stack([int_to_limbs(ref.G2_GEN[1].a), int_to_limbs(ref.G2_GEN[1].b)])


# == On-device committee aggregation =======================================
# The aggregation half of BLS verification (sum of 135 signature points +
# 135 pubkeys per shard — host-side python point adds in r1, ~0.7 s per
# 100-shard audit) moves on device as a masked tree reduction over the
# committee axis. Point addition is the COMPLETE projective formula set of
# Renes–Costello–Batina 2016 (algorithm 7, a = 0): branchless, no special
# cases for infinity/doubling/negation — exactly what a batched masked
# kernel needs (padded slots are the identity (0:1:0); duplicate pubkeys
# hit the doubling path of the same formulas). The reference's analog is
# the scalar `PairingCheck` caller doing per-vote adds in Go
# (crypto/bn256/cloudflare/curve.go Add); this is the batch-first rework.

_B3_G2 = (ref.B2.scalar(3))  # 3·b' = 9/ξ on the D-twist y² = x³ + 3/ξ
_B3_G2_LIMBS = _const_fp2(_B3_G2.a, _B3_G2.b)


def _proj_add_impl(x1, y1, z1, x2, y2, z2, mul_many, add, sub, mul_b3):
    """RCB16 algorithm 7 (a = 0 short Weierstrass, projective X:Y:Z).

    Complete: handles identity (0:1:0), doubling and inverse pairs with
    no branches. Field ops are abstract (Fp or Fp2); the 12 field
    products run as THREE stacked batched muls via `mul_many`
    (independent products share one normalize chain each), which keeps
    the 8-level committee tree's op count flat."""
    t0, t1, t2 = mul_many([(x1, x2), (y1, y2), (z1, z2)])
    m3, m4, m5 = mul_many([(add(x1, y1), add(x2, y2)),
                           (add(y1, z1), add(y2, z2)),
                           (add(x1, z1), add(x2, z2))])
    t3 = sub(m3, add(t0, t1))        # x1y2 + x2y1
    t4 = sub(m4, add(t1, t2))        # y1z2 + y2z1
    t5 = sub(m5, add(t0, t2))        # x1z2 + x2z1
    t0 = add(add(t0, t0), t0)        # 3·x1x2
    t2 = mul_b3(t2)                  # b3·z1z2
    zs = add(t1, t2)                 # y1y2 + b3z1z2
    t1 = sub(t1, t2)                 # y1y2 - b3z1z2
    yb = mul_b3(t5)                  # b3·(x1z2 + x2z1)
    p1, p2, p3, p4, p5, p6 = mul_many([
        (t3, t1), (t4, yb), (t1, zs), (t0, yb), (zs, t4), (t0, t3)])
    return sub(p1, p2), add(p3, p4), add(p5, p6)


def _g1_proj_add(p1, p2):
    def mul_many(pairs):
        xs = jnp.stack([a for a, _ in pairs], axis=-2)
        ys = jnp.stack([b for _, b in pairs], axis=-2)
        out = FP.mul(xs, ys)
        return [out[..., i, :] for i in range(len(pairs))]

    return _proj_add_impl(*p1, *p2, mul_many=mul_many, add=FP.add,
                          sub=FP.sub, mul_b3=lambda v: FP.mul_small(v, 9))


def _g2_proj_add(p1, p2):
    b3 = jnp.asarray(_B3_G2_LIMBS)

    def mul_many(pairs):
        xs = jnp.stack([a for a, _ in pairs], axis=-3)
        ys = jnp.stack([b for _, b in pairs], axis=-3)
        out = fp2_mul(xs, ys)
        return [out[..., i, :, :] for i in range(len(pairs))]

    return _proj_add_impl(*p1, *p2, mul_many=mul_many, add=fp2_add,
                          sub=fp2_sub, mul_b3=lambda v: fp2_mul(v, b3))


def _tree_reduce_pow2(point, axis, add_fn):
    """Sum (X, Y, Z) coordinate stacks along committee axis `axis`
    (negative, counted from the end; the same for all three coords) by
    repeated halving; the axis length must be a power of two here."""
    px, py, pz = point
    while px.shape[axis] > 1:
        half = px.shape[axis] // 2

        def split(a):
            lo = jnp.take(a, np.arange(half), axis=axis)
            hi = jnp.take(a, np.arange(half, 2 * half), axis=axis)
            return lo, hi

        (xl, xh), (yl, yh), (zl, zh) = split(px), split(py), split(pz)
        px, py, pz = add_fn((xl, yl, zl), (xh, yh, zh))
    return (jnp.squeeze(px, axis), jnp.squeeze(py, axis),
            jnp.squeeze(pz, axis))


def _tree_reduce(point, axis, add_fn):
    """Point sum along `axis` for ANY width: the width's binary
    decomposition gives power-of-two segments (135 -> 128+4+2+1), each
    tree-reduced, partial sums folded in — C-1 adds total instead of
    the up-to-2x of padding to the next power of two."""
    px, py, pz = point
    width = px.shape[axis]
    if width == 0:
        raise ValueError("empty committee axis")
    partials = []
    start = 0
    while start < width:
        size = 1 << ((width - start).bit_length() - 1)
        seg = tuple(
            jnp.take(a, np.arange(start, start + size), axis=axis)
            for a in (px, py, pz))
        partials.append(_tree_reduce_pow2(seg, axis, add_fn))
        start += size
    acc = partials[0]
    for part in partials[1:]:
        acc = add_fn(acc, part)
    return acc


def aggregate_g1_proj(xs, ys, mask):
    """Masked committee sum of G1 points, on device.

    xs/ys: (..., C, 22) affine limbs; mask: (..., C) bool (False slots
    contribute the identity); any C >= 1. Returns the projective
    (X, Y, Z) sum, each (..., 22)."""
    if AGG == "mega" and _limb._pallas_wanted():
        from gethsharding_tpu.ops.pallas_finalexp import aggregate_proj

        return aggregate_proj(xs, ys, mask, fp2=False)
    m = mask[..., None]
    one = jnp.broadcast_to(jnp.asarray(FP.one), xs.shape)
    px = jnp.where(m, xs, 0)
    py = jnp.where(m, ys, one)
    pz = jnp.where(m, one, 0)
    return _tree_reduce((px, py, pz), -2, _g1_proj_add)


def aggregate_g2_proj(xs, ys, mask):
    """Masked committee sum of G2 points: xs/ys (..., C, 2, 22)."""
    if AGG == "mega" and _limb._pallas_wanted():
        from gethsharding_tpu.ops.pallas_finalexp import aggregate_proj

        return aggregate_proj(xs, ys, mask, fp2=True)
    m = mask[..., None, None]
    one = jnp.broadcast_to(jnp.asarray(FP2_ONE), xs.shape)
    px = jnp.where(m, xs, 0)
    py = jnp.where(m, ys, one)
    pz = jnp.where(m, one, 0)
    return _tree_reduce((px, py, pz), -3, _g2_proj_add)


def bls_verify_aggregate_batch(hx, hy, sx, sy, pkx, pky, valid,
                               pallas=None):
    """Batched BLS aggregate-vote verification (BASELINE.md config 2/3).

    For each batch element b: e(sig_b, G2_GEN) == e(H_b, aggpk_b), checked
    as e(sig, G2)·e(-H, pk) == 1 via the shared-accumulator optimal-ate
    Miller loop and the inversion-free final check.
    hx/hy, sx/sy: (..., 22) G1 limbs (message hash, aggregate signature);
    pkx/pky: (..., 2, 22) G2 limbs (aggregate public key);
    valid: (...,) bool — invalid rows (infinity/malformed, rejected
    host-side) return False.
    `pallas`: the final exponentiation's kernel, as in
    `pairing_in_pallas` (the affine walk is XLA's).
    Returns (...,) bool.
    """
    f = _bls_miller_opt((sx, sy, None), hx, hy, (pkx, pky, None))
    return pairing_is_one(f, pallas) & valid


def bls_aggregate_verify_committee_batch(hx, hy, sigx, sigy, sig_mask,
                                         pkx, pky, pk_mask, valid,
                                         pallas=None):
    """Aggregate AND verify per-shard committee votes in one dispatch.

    The full notary hot-loop kernel: per batch row (= shard), sum the
    masked committee signature points (G1) and voter pubkeys (G2) with
    the complete projective tree reduction, then run the optimal-ate
    check e(aggsig, G2)·e(-H, aggpk) == 1 directly on the projective
    aggregates — no host aggregation, no field inversion anywhere.

    hx/hy: (B, 22) message-hash limbs; sigx/sigy: (B, C, 22) vote
    signatures with sig_mask (B, C); pkx/pky: (B, C, 2, 22) registered
    voter pubkeys with pk_mask (B, C); any C >= 1 (pad rows masked).
    Identity aggregates (empty committee or adversarial cancellation)
    are rejected, matching the scalar `bls_verify_aggregate`.
    `pallas`: the Miller walk's and the final exponentiation's kernels,
    as in `pairing_in_pallas`.
    Returns (B,) bool.
    """
    # the scopes are metadata only: they name the four stages in the
    # lowered program, and in the op names of a device trace, so that a
    # reduction of the trace finds each after a refactor
    with jax.named_scope("bls/g1_aggregate"):
        sX, sY, sZ = aggregate_g1_proj(sigx, sigy, sig_mask)
    with jax.named_scope("bls/g2_aggregate"):
        pX, pY, pZ = aggregate_g2_proj(pkx, pky, pk_mask)
    inf = FP.is_zero(sZ) | fp2_is_zero(pZ)
    with jax.named_scope("bls/miller"):
        f = _bls_miller_opt((sX, sY, sZ), hx, hy, (pX, pY, pZ), pallas)
    with jax.named_scope("bls/final_exp"):
        one = pairing_is_one(f, pallas)
    return one & valid & ~inf


# == Fixed-base pairing precomputation =====================================
# Every committee audit pairs against two arguments that are FIXED across
# dispatches: the G2 generator (static — `_GEN_LINES`, precomputed on the
# host at import) and the committee's aggregate pubkey (content-stable per
# `pk_row_key`, warm in the resident LRU). Yet `_bls_miller_opt` re-runs
# the doubling/addition point arithmetic for the pk walk on every call.
# `precompute_lines` runs that schedule ONCE and emits the dense
# line-coefficient table; `miller_loop_precomp` consumes it, degenerating
# the hot loop to sparse fp12 line evaluations + multiplications. The
# stored coefficients are the EXACT limb arrays the recompute path feeds
# to the same `fp2_mul_fp`/`fp12_mul_line` primitives in the same order,
# so verdicts are bit-identical by construction (asserted against the
# scalar twin in tests/test_sigbackend_precomp.py).

# line-coefficient table shape per batch element: one (c_py, c_px,
# c_const) Fp2 triple per optimal-ate schedule step
LINE_TABLE_SHAPE = (len(_OPT_OPS), 3, 2, NLIMBS)


def generator_line_table():
    """Static G2-generator line table (L, 3, 2, 22), host int32 copy.

    The per-step (c_py, c_px, c_const) coefficients of the generator
    walk — the fixed half of every pairing, precomputed at import. The
    backend ships this to device once at construction."""
    return np.array(_GEN_LINES)


def precompute_lines(pkx, pky, pkz):
    """Run the optimal-ate point-arithmetic schedule ONCE for a fixed
    projective G2 argument and emit its dense line-coefficient table.

    pkx/pky/pkz: (..., 2, 22) projective G2 limbs (the aggregate-pubkey
    output of `aggregate_g2_proj`). Returns (..., L, 3, 2, 22) int32:
    per schedule step the raw (c_py, c_px, c_const) coefficients that
    `_dbl_coeffs`/`_jadd_coeffs` would produce inline — NOT evaluated
    against any G1 point, so one table serves every future message.
    Candidate setup and walk start replicate `_bls_miller_opt`'s
    projective branch exactly; the trajectory (and hence every stored
    coefficient) is bitwise the arrays the recompute path evaluates.
    """
    shape = pkx.shape[:-2]
    q1x = fp2_mul(fp2_conj(pkx), jnp.asarray(_TWF_X))
    q1y = fp2_mul(fp2_conj(pky), jnp.asarray(_TWF_Y))
    q2x = fp2_mul(pkx, jnp.asarray(_TWF2_X))
    q2ny = FP.neg(fp2_mul(pky, jnp.asarray(_TWF2_Y)))
    proj_x = [pkx, pkx, q1x, q2x]
    proj_y = [pky, FP.neg(pky), q1y, q2ny]
    zconj = fp2_conj(pkz)
    proj_z = [pkz, pkz, zconj, pkz]
    jac = []
    for cx, cy, cz in zip(proj_x, proj_y, proj_z):
        zz = fp2_sqr(cz)
        jac.append((fp2_mul(cx, cz), fp2_mul(cy, zz), cz, zz,
                    fp2_mul(cz, zz)))
    cand = tuple(jnp.stack([j[k] for j in jac]) for k in range(5))

    X = fp2_mul(pkx, pkz)
    Y = fp2_mul(pky, fp2_sqr(pkz))
    Z = FP.normalize(jnp.broadcast_to(pkz, shape + (2, NLIMBS)))

    def dbl_branch(X, Y, Z, op):
        return _dbl_coeffs(X, Y, Z)

    def add_branch(X, Y, Z, op):
        q2 = tuple(
            lax.dynamic_index_in_dim(c, op - 1, axis=0, keepdims=False)
            for c in cand)
        return _jadd_coeffs(X, Y, Z, q2)

    def step(carry, op):
        X, Y, Z = carry
        coeffs, X, Y, Z = lax.cond(op == 0, dbl_branch, add_branch,
                                   X, Y, Z, op)
        return (X, Y, Z), jnp.stack(coeffs, axis=-3)

    (X, Y, Z), lines = lax.scan(step, (X, Y, Z), jnp.asarray(_OPT_OPS))
    return jnp.moveaxis(lines, 0, -4)


def precompute_g2_lines(pkx, pky, pk_mask):
    """Aggregate a committee pk row and precompute its line table.

    pkx/pky: (..., C, 2, 22) voter pubkeys, pk_mask (..., C). Returns
    (table (..., L, 3, 2, 22), pk_inf (...,) bool) — pk_inf marks
    identity aggregates (empty committee / adversarial cancellation),
    whose rows the consumer must reject exactly as the recompute path
    does via its `fp2_is_zero(pZ)` term. The table for such a row is
    well-defined garbage (pure limb arithmetic, no inversion) and never
    reaches a verdict.
    """
    pX, pY, pZ = aggregate_g2_proj(pkx, pky, pk_mask)
    return precompute_lines(pX, pY, pZ), fp2_is_zero(pZ)


def miller_loop_precomp(sig, hx, hy, table, gen_lines=None):
    """Optimal-ate Miller product consuming a precomputed line table —
    the fixed-argument point arithmetic is GONE from the hot loop.

    sig = (sx, sy, sz) projective aggregate-signature G1 limbs,
    hx/hy (..., 22) message-hash limbs, table (..., L, 3, 2, 22) from
    `precompute_lines`. Per step: conditional fp12_sqr, one sparse
    generator-line multiply, one sparse pk-line multiply — the same
    three f-updates `_bls_miller_opt` performs, fed bitwise-identical
    line operands, so the returned f (and any verdict derived from it)
    is bit-identical to the recompute path's.

    `gen_lines`: the (L, 3, 2, 22) generator table — pass the
    backend's device-resident copy (`generator_line_table()` shipped
    once at construction) so every compiled shape shares ONE buffer;
    None embeds the module constant (value-identical).
    """
    sx, sy, sz = sig
    shape = sx.shape[:-1]
    hy_neg = FP.neg(hy)
    if gen_lines is None:
        gen_lines = jnp.asarray(_GEN_LINES)
    vzero = (sx[..., :1] * 0)[..., None]           # (..., 1, 1)
    f = FP.normalize(jnp.broadcast_to(jnp.asarray(FP12_ONE),
                                      shape + (6, 2, NLIMBS)) + vzero[..., None])

    def gen_line(line_c):
        A = fp2_mul_fp(line_c[0], sy)
        B = fp2_mul_fp(line_c[1], sx)
        C = jnp.broadcast_to(FP.normalize(line_c[2]), shape + (2, NLIMBS))
        if sz is not None:
            C = fp2_mul_fp(C, sz)
        return A, B, C

    def pk_line(tab_c):
        """Stored (c_py, c_px, c_const) evaluated at -H — exactly the
        `line = (c_py·py, c_px·px, c_const)` the step kernels build."""
        A = fp2_mul_fp(tab_c[..., 0, :, :], hy_neg)
        B = fp2_mul_fp(tab_c[..., 1, :, :], hx)
        C = tab_c[..., 2, :, :]
        return A, B, C

    def step(f, xs):
        op, line_c, tab_c = xs
        f = lax.cond(op == 0, fp12_sqr, lambda v: v, f)
        f = fp12_mul_line(f, gen_line(line_c))
        f = fp12_mul_line(f, pk_line(tab_c))
        return f, None

    f, _ = lax.scan(
        step, f,
        (jnp.asarray(_OPT_OPS), gen_lines, jnp.moveaxis(table, -4, 0)))
    return f


def bls_committee_precomp_miller(hx, hy, sigx, sigy, sig_mask,
                                 table, pk_inf, valid, gen_lines=None):
    """Miller stage of the precomp committee audit: aggregate the vote
    signatures on device, then run the table-fed Miller loop. Returns
    (f (..., 6, 2, 22), ok (...,) bool) — split from the finalexp stage
    so dispatch can pipeline lane blocks of the next Miller against the
    finalexp mega-kernel of the previous block."""
    sX, sY, sZ = aggregate_g1_proj(sigx, sigy, sig_mask)
    ok = valid & ~(FP.is_zero(sZ) | pk_inf)
    f = miller_loop_precomp((sX, sY, sZ), hx, hy, table,
                            gen_lines=gen_lines)
    return f, ok


def bls_committee_precomp_finalexp(f, ok, pallas=None):
    """Finalexp stage of the precomp committee audit."""
    return pairing_is_one(f, pallas) & ok


def bls_verify_committee_precomp_batch(hx, hy, sigx, sigy, sig_mask,
                                       table, pk_inf, valid,
                                       gen_lines=None, pallas=None):
    """Precomp twin of `bls_aggregate_verify_committee_batch`: the G2
    aggregation and the fixed-argument point arithmetic were paid once
    in `precompute_g2_lines`; this consumes the resident table. Verdicts
    are bit-identical to the recompute kernel for the same committee
    content (same primitives, same operands, same order). `pallas`: the
    final exponentiation's kernel, as in `pairing_in_pallas` (the
    table-fed walk is XLA's).
    Returns (B,) bool."""
    f, ok = bls_committee_precomp_miller(hx, hy, sigx, sigy, sig_mask,
                                         table, pk_inf, valid,
                                         gen_lines=gen_lines)
    return bls_committee_precomp_finalexp(f, ok, pallas)


# == Fixed-base MSM over the SRS powers: the multiproof check ==============
# A multiproof row (das/pcs.py) checks e(C − [r(τ)]₁, G2)·e(−π, [z_S(τ)]₂)
# == 1, where [r(τ)]₁ = Σ c_k·[τ^k]₁ and [z_S(τ)]₂ = Σ z_k·[τ^k]₂ are MSMs
# over the SRS powers with the row's interpolation and vanishing
# coefficients. The bases never change, so each power gets a table of its
# windowed multiples, built ONCE per SRS on the device: entry (k, j, d) =
# d·2^(w·j)·[τ^k]. A scalar's MSM term is then W = 256/w gathered entries
# (one per w-bit window), and the row's MSM is one tree sum of the
# gathered points with the committee kernel's complete projective adders.
# No scalar multiplication runs anywhere; a zero digit gathers the
# identity (0:1:0), so zero coefficients and padded terms cost nothing
# but their slot in the sum.

MSM_WINDOW = 4                    # bits a digit: 16 entries a window
MSM_WINDOWS = 256 // MSM_WINDOW   # digits a scalar (N < 2^254)


def _fixed_base_table(xs, ys, one, add_fn):
    """Windowed multiples of K affine bases, on the device.

    xs/ys: (K, *coord) affine limbs; `one` the coordinate's 1. Returns
    (X, Y, Z) each (K·W·2^w, *coord): entry (k·W + j)·2^w + d holds
    d·2^(w·j)·base_k in projective form, d = 0 the identity. A window's
    multiples 1..2^w come from w batched adds (1 → 2 → 4 → ... by adding
    the top multiple to all below it), and 2^w·base is the next window's
    base."""
    size = 1 << MSM_WINDOW
    one = jnp.broadcast_to(jnp.asarray(one), xs.shape)
    zero = jnp.zeros_like(xs)

    def step(base, _):
        mult = tuple(c[None] for c in base)           # multiples 1..h
        while mult[0].shape[0] < size:
            top = tuple(c[-1:] for c in mult)
            more = add_fn(mult, tuple(jnp.broadcast_to(t, m.shape)
                                      for t, m in zip(top, mult)))
            mult = tuple(jnp.concatenate([m, n]) for m, n in zip(mult, more))
        ident = (zero[None], one[None], zero[None])
        row = tuple(jnp.concatenate([i, m[:-1]]) for i, m in zip(ident, mult))
        return tuple(m[-1] for m in mult), row

    base = (xs, ys, one)
    _, rows = lax.scan(step, base, None, length=MSM_WINDOWS)
    # (W, 2^w, K, *coord) -> (K, W, 2^w, *coord) -> flat entries
    return tuple(
        jnp.moveaxis(r, 2, 0).reshape((-1,) + xs.shape[1:]) for r in rows)


def das_poly_tables(g1x, g1y, g2x, g2y):
    """The SRS's fixed-base tables, (X, Y, Z) stacked on axis 1:
    g1 (K1·W·2^w, 3, 22) and g2 (K2·W·2^w, 3, 2, 22) from the affine
    powers [τ^k]₁ (K1, 22) and [τ^k]₂ (K2, 2, 22)."""
    t1 = _fixed_base_table(g1x, g1y, FP.one, _g1_proj_add)
    t2 = _fixed_base_table(g2x, g2y, FP2_ONE, _g2_proj_add)
    return jnp.stack(t1, axis=1), jnp.stack(t2, axis=1)


def fixed_base_msm(table, digits, add_fn):
    """Σ_k scalar_k·base_k per row from a `das_poly_tables` table.

    table: (K·W·2^w, 3, *coord); digits: (B, S, W) little-endian w-bit
    digits of S scalars a row, S ≤ K. Returns the projective (X, Y, Z)
    sums, each (B, *coord)."""
    rows, terms, windows = digits.shape
    slot = (np.arange(terms)[:, None] * windows
            + np.arange(windows)[None, :]) << MSM_WINDOW
    idx = jnp.asarray(slot, jnp.int32) + digits.astype(jnp.int32)
    pts = jnp.take(table, idx.reshape(rows, terms * windows), axis=0)
    axis = -1 - (table.ndim - 2)                       # the term axis
    return _tree_reduce((pts[:, :, 0], pts[:, :, 1], pts[:, :, 2]), axis,
                        add_fn)


def das_poly_verify_batch(cx, cy, c_inf, px, py, p_inf, r_digits, z_digits,
                          valid, g1_table, g2_table, pallas=None):
    """Batched multiproof check, MSMs included: per row
    e(C − R, G2_GEN)·e(−π, Z) == 1 with R = [r(τ)]₁, Z = [z_S(τ)]₂ summed
    on the device from the resident SRS tables.

    cx/cy: (B, 22) commitment limbs, c_inf (B,) its infinity flag;
    px/py, p_inf: the proof π likewise; r_digits (B, S, W) and
    z_digits (B, S+1, W): the interpolation and vanishing coefficients'
    digits (zero-padded); valid (B,) the host's shape and decode checks.

    Infinity follows the scalar `pcs.verify_multi` exactly: its pairing
    skips a pair with a point at infinity, and a pair of non-infinite
    G1 x G2 points never pairs to 1. So a row whose A = C − R is at
    infinity holds iff its second pair is skipped too (π or Z at
    infinity), a row with only the second pair skipped fails, and the
    others take the pairing's verdict. `pallas`: the pairing's
    kernels, as in `pairing_in_pallas`. Returns (B,) bool."""
    with jax.named_scope("das/poly_msm_g1"):
        rX, rY, rZ = fixed_base_msm(g1_table, r_digits, _g1_proj_add)
    with jax.named_scope("das/poly_msm_g2"):
        zX, zY, zZ = fixed_base_msm(g2_table, z_digits, _g2_proj_add)
    m = c_inf[..., None]
    one = jnp.broadcast_to(jnp.asarray(FP.one), cx.shape)
    c = (jnp.where(m, 0, cx), jnp.where(m, one, cy), jnp.where(m, 0, one))
    aX, aY, aZ = _g1_proj_add(c, (rX, FP.neg(rY), rZ))
    a_inf = FP.is_zero(aZ)
    skip = p_inf | fp2_is_zero(zZ)
    with jax.named_scope("bls/miller"):
        f = _bls_miller_opt((aX, aY, aZ), px, py, (zX, zY, zZ), pallas)
    with jax.named_scope("bls/final_exp"):
        one_f = pairing_is_one(f, pallas)
    return jnp.where(a_inf | skip, a_inf & skip, one_f) & valid


# == host-side converters ==================================================


def g1_to_limbs(points: Sequence[ref.G1Point]):
    """[(x, y) | None]* -> (xs, ys, valid): (B, 22) int32 ×2 + (B,) bool.

    Infinity/None encodes as (0, 0) with valid=False — callers decide
    whether that means "skip the pair" (mask) or "reject the row"."""
    xs, ys, ok = [], [], []
    for pt in points:
        if pt is None:
            xs.append(0), ys.append(0), ok.append(False)
        else:
            xs.append(pt[0] % P), ys.append(pt[1] % P), ok.append(True)
    return (ints_to_limbs(xs), ints_to_limbs(ys), np.asarray(ok))


def g2_to_limbs(points: Sequence[ref.G2Point]):
    """G2 affine points -> (xs, ys, valid): (B, 2, 22) ×2 + (B,) bool."""
    xs, ys, ok = [], [], []
    for pt in points:
        if pt is None:
            xs.append(np.zeros((2, NLIMBS), np.int32))
            ys.append(np.zeros((2, NLIMBS), np.int32))
            ok.append(False)
        else:
            x, y = pt
            xs.append(np.stack([int_to_limbs(x.a), int_to_limbs(x.b)]))
            ys.append(np.stack([int_to_limbs(y.a), int_to_limbs(y.b)]))
            ok.append(True)
    return (np.stack(xs), np.stack(ys), np.asarray(ok))


def msm_digits(rows, terms: int) -> np.ndarray:
    """Scalar rows -> the (B, terms, W) uint8 digit plane of
    `fixed_base_msm`: row b's scalar k as W little-endian w-bit digits,
    zero-padded to `terms`; a None row is all zeros (the identity)."""
    raw = bytearray(len(rows) * terms * 32)
    for b, row in enumerate(rows):
        for k, value in enumerate(row or ()):
            at = (b * terms + k) * 32
            raw[at:at + 32] = (value % N).to_bytes(32, "little")
    bits = np.unpackbits(np.frombuffer(bytes(raw), np.uint8),
                         bitorder="little")
    bits = bits.reshape(len(rows), terms, MSM_WINDOWS, MSM_WINDOW)
    weights = (1 << np.arange(MSM_WINDOW)).astype(np.uint8)
    return (bits * weights).sum(axis=-1, dtype=np.uint8)


_COORD_BYTES = pointrows.COORD_BYTES
_LIMB_BYTES = -(-NLIMBS * _limb.LIMB_BITS // 8)  # of `limb.ints_to_bytes`
_P_WORDS = np.frombuffer(P.to_bytes(_COORD_BYTES, "big"), ">u8")
_P_TOP_BYTE = P >> (8 * (_COORD_BYTES - 1))
# rows that took the integer entry inside a conversion that had packed
# rows: listed rows beside packed ones, and the `>= P` fallback
_INT_ROWS = metrics.counter("sig/marshal/int_rows")


def _not_below_p(be: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 big-endian coordinates -> (n,) bool: value >= P."""
    w = be.view(">u8")
    ge = w[:, 3] >= _P_WORDS[3]
    for i in (2, 1, 0):
        ge = (w[:, i] > _P_WORDS[i]) | ((w[:, i] == _P_WORDS[i]) & ge)
    return ge


def _int_row(le: np.ndarray, mask: np.ndarray, b: int, row, half: int):
    """The integer entry: one row of Python points (None = empty slot),
    reduced mod P, into row `b` of the byte plane and the mask."""
    xs, ys = [], []
    for c, pt in enumerate(row):
        if pt is None:
            xs.extend((0,) * half)
            ys.extend((0,) * half)
            continue
        if half == 2:
            x, y = pt
            xs.extend((x.a % P, x.b % P))
            ys.extend((y.a % P, y.b % P))
        else:
            xs.append(pt[0] % P)
            ys.append(pt[1] % P)
        mask[b, c] = True
    le[:, b, :len(row)] = _limb.ints_to_bytes(xs + ys).reshape(
        2, len(row), half, _LIMB_BYTES)


def _committee_to_limbs(rows, width: int, out_dtype, point_size: int):
    """One algorithm, two entries, chosen by the row's type: every row's
    coordinates are laid little-endian into ONE byte plane, a
    `PackedRow` by one `np.frombuffer` and a byte reversal, any other
    row point by point through the integers (`_int_row`); then one
    bit-plane pass for x and y (`limb.bytes_to_limbs`). The planes are
    those of the integer entry bit for bit: `% P` is kept exactly, a
    packed row with a coordinate not below P re-enters through the
    integers."""
    half = point_size // (2 * _COORD_BYTES)  # Fp coordinates of x (of y)
    B = len(rows)
    le = np.zeros((2, B, width, half, _LIMB_BYTES), np.uint8)
    mask = np.zeros((B, width), bool)
    packed = int_rows = 0
    for b, row in enumerate(rows):
        k = len(row)
        if k > width:
            raise ValueError(f"committee of {k} exceeds width {width}")
        if not k:
            continue
        if isinstance(row, pointrows.PackedRow) \
                and row.point_size == point_size:
            arr = np.frombuffer(row.raw, np.uint8).reshape(
                k, 2, half, _COORD_BYTES)
            le[:, b, :k, :, :_COORD_BYTES] = \
                arr.transpose(1, 0, 2, 3)[..., ::-1]
            mask[b, :k] = True
            packed += 1
        else:
            _int_row(le, mask, b, row, half)
            int_rows += 1
    if packed:
        # only a coordinate whose top byte reaches P's can reach P
        # (and none that came through the integers does)
        at = np.nonzero(le[..., _COORD_BYTES - 1] >= _P_TOP_BYTE)
        over = _not_below_p(np.ascontiguousarray(
            le[at][:, _COORD_BYTES - 1::-1]))
        for b in sorted(set(at[1][over].tolist())):
            le[:, b] = 0
            _int_row(le, mask, b, rows[b], half)
            int_rows += 1
        _INT_ROWS.inc(int_rows)
    both = _limb.bytes_to_limbs(le.reshape(-1, _LIMB_BYTES),
                                out_dtype=out_dtype)
    xs, ys = both.reshape((2, B, width) + ((2, NLIMBS) if half == 2
                                           else (NLIMBS,)))
    return xs, ys, mask


def g1_committee_to_limbs(rows: Sequence[Sequence[ref.G1Point]], width: int,
                          out_dtype=np.int32):
    """B rows of ≤width G1 points (None = empty slot; a row may be a
    `crypto.pointrows.PackedRow`) -> the committee kernel inputs
    (B, width, 22) ×2 + mask (B, width). Vectorized through the bulk
    bit-plane path — this sits on the audit's host marshalling critical
    path (B·width points per dispatch). `out_dtype=np.uint16` marshals
    directly into the u16 wire format (canonical 12-bit limbs) without
    a second full-plane copy."""
    return _committee_to_limbs(rows, width, out_dtype,
                               pointrows.G1_POINT_BYTES)


def g2_committee_to_limbs(rows: Sequence[Sequence[ref.G2Point]], width: int,
                          out_dtype=np.int32):
    """B rows of ≤width G2 points -> (B, width, 2, 22) ×2 + mask.

    The audit's LARGEST host buffers (the G2 share of every dispatch);
    rows and `out_dtype` as in `g1_committee_to_limbs`."""
    return _committee_to_limbs(rows, width, out_dtype,
                               pointrows.G2_POINT_BYTES)


# tower-order interop: w-coeff k ↔ tower slot (h, l) with k = 2l + h
_WSLOT = [(0, 0), (1, 0), (0, 1), (1, 1), (0, 2), (1, 2)]  # wᵏ -> (h, l)


def fp12_from_tower(arr: np.ndarray) -> np.ndarray:
    """(..., 2, 3, 2, 22) tower layout -> (..., 6, 2, 22) w-basis."""
    return np.stack([arr[..., h, l, :, :] for (h, l) in _WSLOT], axis=-3)


def fp12_to_int_coeffs(x) -> np.ndarray:
    """Canonical integer coefficients (..., 2, 3, 2) in TOWER order
    (c0/c1 × v-power × Fp2 component) for host comparison with the scalar
    reference classes."""
    w = FP.to_ints(np.asarray(FP.canon(x)))  # (..., 6, 2) object ints
    out = np.zeros(w.shape[:-2] + (2, 3, 2), object)
    for k, (h, l) in enumerate(_WSLOT):
        out[..., h, l, :] = w[..., k, :]
    return out
