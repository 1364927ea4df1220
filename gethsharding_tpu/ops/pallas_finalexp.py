"""Pallas TPU mega-kernel: the ENTIRE final exponentiation in one kernel.

The audit dispatch is latency-bound, not flops-bound (PERF.md): the
final exponentiation alone is ~250 sequential fp12 operations, and as
stock XLA each is a chain of kernels with a serialized carry scan inside
every normalize — per-op dispatch and HBM round-trips dominate. This
kernel runs the whole inversion-free fraction-stacked final-exp program
(`bn256_jax.pairing_is_one`: easy part, three x^u NAF ladders, the
Devegili–Scott–Dahab hard part) as ONE `pallas_call`:

- a VMEM-resident register file (14 registers × fraction 2 × 12 Fp
  coefficients × 25 limbs × batch lanes, ~5 MB at the 128-lane block);
- a `fori_loop` over a ~250-instruction program held in SMEM, each step
  dispatching mul / swap / frobenius / copy via `pl.when` — the kernel
  compiles each op ONCE, the loop replays it with zero launch overhead;
- RELAXED normalization everywhere (value-preserving carry rounds as
  full-tile vector ops; quasi-canonical limbs in [-1, 2^12+64]) — the
  kernel contains no sequential carry chain at all;
- batch on lanes, limbs/planes on sublanes:
  every shift-MAC of the schoolbook convolution is a full-width vector
  op across all 288 product planes of an fp12 product at once.

The arithmetic is self-contained wide-form (25 limbs) regardless of the
ambient GETHSHARDING_TPU_* knobs: inputs arrive as any lazy limb form
(22 or 25 wide, value < 2^273) and outputs return as 25-limb
quasi-canonical limbs which the XLA wrapper re-normalizes into the
ambient form. Bound proofs: the quasi-canonical bound and the
fold/lift constants at `_LIFT_RELAXED` and `_normalize` below.

Reference parity: this replaces the final-exponentiation half of
`crypto/bn256/cloudflare/optate.go` (finalExponentiation) whose field
stack is hand-written assembly (`gfp_amd64.s:39-129`) — the reference's
answer to the same problem (fuse the whole field stack below the
dispatch boundary), re-expressed for a systolic/vector machine.

`bn256_jax.pairing_is_one` runs `finalexp_is_one`, and the projective
`bn256_jax._bls_miller_opt` runs `miller_f`, on every platform but the
CPU unless the caller passes `pallas=False` (`bn256_jax.pairing_in_pallas`:
the mesh does). Differential tests run the kernel in interpreter mode on CPU against the
XLA path (tests/test_pallas_finalexp.py), and `run_program_xla` executes
the same instruction stream with the same helpers as plain XLA ops so
program-logic bugs and Pallas-mechanics bugs isolate cleanly.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from gethsharding_tpu.crypto import bn256 as ref
from gethsharding_tpu.ops.limb import LIMB_BITS, LIMB_MASK, int_to_limbs

BLOCK_LANES = 128

# == self-contained wide-relaxed limb constants ============================
# The kernel always computes in the 25-limb wide form with relaxed
# normalization, independent of the ambient knobs (a 22-limb ambient form
# converts losslessly on the way in/out). Constants re-derived here with
# the same formulas as limb.ModArith.__init__ so the bound proofs carry.

P = ref.P
KNL = 25                      # kernel limb count (wide form)
KFOLD_BASE = 22
KFOLD_ROWS = 33
KNCOLS = 2 * KNL - 1          # schoolbook product columns (49)

_FOLD_J = np.stack(
    [int_to_limbs(pow(1 << (LIMB_BITS * (KFOLD_BASE + k)), 1, P),
                  KFOLD_BASE)
     for k in range(KFOLD_ROWS)]).astype(np.int32)     # (33, 22)

# lift added after the fold (multiple of p covering the worst-case
# negative fold/lo terms of quasi-canonical inputs: the fold acts on
# limbs that can reach -113, so its value can go as low as
# -KFOLD_ROWS·113·p, plus a lo part down to -113·2^253)
_DEFICIT = KFOLD_ROWS * 113 * P + (113 << 253)
_LIFT_RELAXED = int_to_limbs(-(-_DEFICIT // P) * P, KNL)


def _pad_mult(bits: int) -> np.ndarray:
    value = -(-(1 << bits) // P) * P
    nlimbs = -(-value.bit_length() // LIMB_BITS)
    return int_to_limbs(value, nlimbs)


_PAD547 = _pad_mult(547)      # >= two subtracted lazy products (46 limbs)
_PAD274 = _pad_mult(274)      # >= one lazy element (value < 2^273)

# row-vector forms (width, 1) for lane-broadcast adds
def _rows(vec: np.ndarray, width: int) -> np.ndarray:
    out = np.zeros((width, 1), np.int32)
    out[: vec.shape[0], 0] = vec
    return out


# conv-accumulator pad: re component subtracts <= 2 products per group
# (same structure as bn256_jax._group_pad); im is all-positive
_MUL_PAD = np.zeros((2, 1, KNCOLS, 1), np.int32)   # (c, g-bcast, cols, 1)
_MUL_PAD[0, 0] = _rows(_PAD547, KNCOLS)
_FP2_PAD = np.zeros((2, KNCOLS, 1), np.int32)      # frobenius fp2 mul
_FP2_PAD[0] = _rows(_PAD547, KNCOLS)
_NEG_PAD = _rows(_PAD274, KNL)                     # for conj / xi diff

# Frobenius constants gamma_{n,k} = xi^(k(p^n-1)/6), 25-limb form
def _const_fp2_25(a: int, b: int) -> np.ndarray:
    return np.stack([int_to_limbs(a % P, KNL), int_to_limbs(b % P, KNL)])


_GAMMA = np.stack([
    np.stack([_const_fp2_25(*(lambda g: (g.a, g.b))(
        ref._fp2_pow(ref.XI, k * (P ** n - 1) // 6)))
        for k in range(6)])
    for n in (1, 2, 3)]).astype(np.int32)          # (3, 6, 2, 25)

# cyclic-convolution index tables (same derivation as bn256_jax)
_CONV_J = np.array([[(k - i) % 6 for i in range(6)] for k in range(6)])
_CONV_SEL = np.array([[0 if i + (k - i) % 6 == k else 1 for i in range(6)]
                      for k in range(6)])


class Consts(NamedTuple):
    """The kernel's numeric constants, threaded explicitly: Pallas
    forbids captured array constants in kernels, so they enter as kernel
    inputs (and as plain arrays on the XLA-oracle path)."""

    fold_t: Any   # (22, 33)  transposed fold matrix (column h = fold row)
    lift: Any     # (25, 1)   relaxed lift (multiple of p)
    mulpad: Any   # (2, 1, 49, 1) fp12-mul group pad (re rows only)
    fp2pad: Any   # (2, 49, 1)    frobenius fp2-mul pad
    negpad: Any   # (25, 1)   negation pad (multiple of p >= 2^274)
    gamma: Any    # (3, 6, 2, 25, 1) Frobenius gamma_{n,k} limbs
    linepad: Any  # (2, 2, 49, 1) sparse line-mul group pad (re rows)
    one12: Any    # (6, 2, 25, 1) the fp12 multiplicative identity


# _LINE_PAD is defined with the Miller helpers below; populated after
def _np_consts() -> "Consts":
    return Consts(
        fold_t=np.ascontiguousarray(_FOLD_J.T),
        lift=_LIFT_RELAXED[:, None],
        mulpad=_MUL_PAD,
        fp2pad=_FP2_PAD,
        negpad=_NEG_PAD,
        gamma=_GAMMA[..., None],
        linepad=_LINE_PAD,
        one12=_ONE12,
    )


# == pure-jnp helpers ======================================================
# All helpers take (..., W, B) blocks — batch on the minor (lane) axis,
# limb index on the second-minor (sublane) axis, anything broadcastable in
# front. They run identically as plain XLA ops (differential tests,
# `run_program_xla`) and inside the Pallas kernel.


def _zeros_like_rows(x, rows: int):
    return jnp.zeros(x.shape[:-2] + (rows, x.shape[-1]), jnp.int32)


def _round(z):
    """One width-preserving relaxed carry round with top-carry refold:
    value-exact for any width (`limb._relaxed_round` with the top carry
    re-fused into the top limb)."""
    lo = z & LIMB_MASK
    c = z >> LIMB_BITS
    shifted = jnp.concatenate(
        [_zeros_like_rows(c, 1), c[..., :-1, :]], axis=-2)
    z2 = lo + shifted
    top_fix = c[..., -1:, :] << LIMB_BITS
    return jnp.concatenate(
        [z2[..., :-1, :], z2[..., -1:, :] + top_fix], axis=-2)


def _normalize(z, C: Consts):
    """Relaxed normalize: (..., W, B) accumulator (|limb| < 2^30.7,
    value >= 0) -> (..., 25, B) quasi-canonical limbs in [-1, 2^12+64],
    value preserved mod p: 2 growing rounds, fold, lift, 3 refold rounds —
    with the growth pre-allocated as zero rows so every round is the
    width-preserving masked form."""
    w = z.shape[-2]
    if w > KFOLD_BASE + KFOLD_ROWS - 2:
        raise ValueError(f"accumulator too wide: {w}")
    lead = z.shape[:-2]
    n = 1
    for d in lead:
        n *= d
    z = z.reshape((n,) + z.shape[-2:])  # rank-3: Mosaic-safe (see _conv)
    z = jnp.concatenate([z, _zeros_like_rows(z, 2)], axis=-2)
    z = _round(_round(z))
    # fold rows >= KFOLD_BASE through the fold matrix (broadcast MACs)
    lo = z[..., :KFOLD_BASE, :]
    hi = z[..., KFOLD_BASE:, :]
    acc = lo
    for h in range(hi.shape[-2]):
        acc = acc + hi[..., h:h + 1, :] * C.fold_t[:, h:h + 1]
    acc = jnp.concatenate(
        [acc, _zeros_like_rows(acc, KNL - KFOLD_BASE)], axis=-2)
    acc = acc + C.lift
    return _round(_round(_round(acc))).reshape(lead + (KNL, z.shape[-1]))


def _conv(u, v):
    """Schoolbook columns: (..., 25, B) x (..., 25, B) -> (..., 49, B),
    leading dims broadcast — a stacked-plane shift-MAC loop (25
    full-tile MACs for ALL planes at once): each
    step lands in its column window through a zero-padded concatenate,
    the window update Mosaic lowers (`dynamic_slice` /
    `dynamic_update_slice`, even at static offsets, it does not).

    Leading dims are FLATTENED around the loop (free reshapes — minor
    dims untouched): the fp12 paths otherwise build rank-7 arrays,
    which interpret mode accepts but real Mosaic may not."""
    lead = jnp.broadcast_shapes(u.shape[:-2], v.shape[:-2])
    n = 1
    for d in lead:
        n *= d
    uf = jnp.broadcast_to(u, lead + u.shape[-2:]).reshape(
        (n,) + u.shape[-2:])
    vf = jnp.broadcast_to(v, lead + v.shape[-2:]).reshape(
        (n,) + v.shape[-2:])
    # the LANE dim broadcasts too (e.g. a B=1 constant against a batch):
    # the elementwise product below carries it
    acc = None
    for l in range(KNL):
        term = uf[:, l:l + 1, :] * vf
        parts = []
        if l:
            parts.append(_zeros_like_rows(term, l))
        parts.append(term)
        tail = KNCOLS - KNL - l
        if tail:
            parts.append(_zeros_like_rows(term, tail))
        shifted = parts[0] if len(parts) == 1 else jnp.concatenate(
            parts, axis=-2)
        acc = shifted if acc is None else acc + shifted
    return acc.reshape(lead + (KNCOLS, acc.shape[-1]))


def _mul_xi(y, C: Consts):
    """xi-multiple of every Fp2 coefficient: y (..., 6, 2, 25, B) ->
    same shape, value-parity with bn256_jax.fp2_mul_xi."""
    a = y[..., 0, :, :]
    b = y[..., 1, :, :]
    rr = a * 9 - b + C.negpad
    ii = a + b * 9
    return _normalize(jnp.stack([rr, ii], axis=-3), C)


def _fp12_mul(x, y, C: Consts):
    """w-basis fp12 product, componentwise over any leading dims.

    x, y: (..., 6, 2, 25, B). Same algorithm as bn256_jax.fp12_mul:
    cyclic convolution with xi wrap, (component, group) accumulators,
    one batched normalize, two-level group merge."""
    xiy = _mul_xi(y, C)
    # operand stack per (k, i): y or xi*y at plane j — static gather
    # into (..., 6k, 6i, 2b, 25, B)
    src = (y, xiy)
    op_rows = []
    for k in range(6):
        op_rows.append(jnp.stack(
            [src[_CONV_SEL[k][i]][..., _CONV_J[k][i], :, :, :]
             for i in range(6)], axis=-4))
    op = jnp.stack(op_rows, axis=-5)
    # cols[..., k, i, a, b, n, B]
    xe = x[..., None, :, :, None, :, :]       # (..., 1, 6i, 2a, 1, 25, B)
    ve = op[..., :, :, None, :, :, :]          # (..., 6k, 6i, 1, 2b, 25, B)
    cols = _conv(xe, ve)                       # (..., 6, 6, 2, 2, 49, B)
    re = cols[..., 0, 0, :, :] - cols[..., 1, 1, :, :]   # (..., 6, 6, 49, B)
    im = cols[..., 0, 1, :, :] + cols[..., 1, 0, :, :]
    # group pairs of i: g = i // 2  -> (..., 6, 3, 49, B). Strided
    # middle-axis slices (re[..., 0::2, :, :]) lower to lax.gather,
    # which Mosaic rejects (>2D); a leading-dim reshape + static index
    # is the supported spelling of the same pairing.
    re_p = re.reshape(re.shape[:-3] + (3, 2) + re.shape[-2:])
    im_p = im.reshape(im.shape[:-3] + (3, 2) + im.shape[-2:])
    re_g = re_p[..., 0, :, :] + re_p[..., 1, :, :]
    im_g = im_p[..., 0, :, :] + im_p[..., 1, :, :]
    acc = jnp.stack([re_g, im_g], axis=-4)     # (..., 6, 2c, 3g, 49, B)
    acc = acc + C.mulpad
    parts = _normalize(acc, C)                 # (..., 6, 2, 3, 25, B)
    merged = _normalize(parts[..., 0, :, :] + parts[..., 1, :, :], C)
    return _normalize(merged + parts[..., 2, :, :], C)


def _frob(x, n, C: Consts):
    """f^(p^n) with a TRACED scalar n in {1,2,3}: conjugate (n odd) then
    multiply each w-coefficient by gamma_{n,k}. x (..., 6, 2, 25, B)."""
    a = x[..., 0, :, :]
    b = x[..., 1, :, :]
    odd = (n % 2) == 1
    b_in = jnp.where(odd, C.negpad - b, b)
    coeff = _normalize(jnp.stack([a, b_in], axis=-3), C)  # (..., 6,2,25,B)
    g = jnp.where(n == 1, C.gamma[0],
                  jnp.where(n == 2, C.gamma[1], C.gamma[2]))  # (6, 2, 25, 1)
    ga = g[..., 0, :, :]                               # (6, 25, 1)
    gb = g[..., 1, :, :]
    ca = coeff[..., 0, :, :]
    cb = coeff[..., 1, :, :]
    rr = _conv(ca, ga)                                 # broadcast over lanes
    rr2 = _conv(cb, gb)
    ii = _conv(ca, gb)
    ii2 = _conv(cb, ga)
    acc = jnp.stack([rr - rr2, ii + ii2], axis=-3)     # (..., 6, 2, 49, B)
    acc = acc + C.fp2pad
    return _normalize(acc, C)


def _swap(x):
    """Fraction inverse: exchange numerator and denominator (axis 0)."""
    return jnp.concatenate([x[1:2], x[0:1]], axis=0)


# == the instruction stream ================================================
# ops: 0 = mul(ra, rb) -> rd; 1 = swap(ra) -> rd; 2 = frob_b(ra) -> rd
# (n in the b field); 3 = copy(ra) -> rd. Registers: 14 fraction-stacked
# fp12 values; r0 holds the easy-part output, r1..r3 the x^u ladder
# results, r4.. the DSD hard-part temps (bn256_jax._HARD_PROGRAM's plan).


def _build_program() -> np.ndarray:
    from gethsharding_tpu.ops.bn256_jax import _HARD_PROGRAM, _U_NAF

    prog = [
        (2, 0, 2, 4),   # r4 = frob2(nd)
        (0, 4, 0, 0),   # nd = frob2(nd) * nd   (easy part, p^2+1)
    ]
    digits = list(reversed(np.asarray(_U_NAF)[:-1].tolist()))
    for s, d in ((0, 1), (1, 2), (2, 3)):   # fu, fu2, fu3
        prog.append((1, s, 0, 4))           # r4 = swap(x): x^-1 for NAF
        prog.append((3, s, 0, d))           # acc = x  (top NAF digit = 1)
        for dig in digits:
            prog.append((0, d, d, d))       # acc = acc^2
            if dig == 1:
                prog.append((0, d, s, d))
            elif dig == -1:
                prog.append((0, d, 4, d))
    for op, a, b, dst in np.asarray(_HARD_PROGRAM).tolist():
        if op == 0:
            prog.append((0, a, b, dst))
        elif op == 1:
            prog.append((0, a, a, dst))     # sqr = mul(a, a)
        elif op == 2:
            prog.append((1, a, 0, dst))     # cyclotomic inverse = swap
        else:
            prog.append((2, a, op - 2, dst))
    return np.asarray(prog, np.int32)


_N_REGS = 14
_RESULT_REG = 13


def _apply_op(regs, op, a, b, d, C: Consts):
    """One instruction on a register list (trace-time dispatch) — the
    XLA twin of the kernel's pl.when dispatch, for differential tests."""
    ra = regs[a]
    if op == 0:
        out = _fp12_mul(ra, regs[b], C)
    elif op == 1:
        out = _swap(ra)
    elif op == 2:
        out = _frob(ra, jnp.int32(b), C)
    else:
        out = ra
    regs[d] = out
    return regs


def run_program_xla(nd):
    """Execute the full program as plain (unrolled) XLA ops.

    nd: (2, n, 6, 2, 25) int32 lazy limbs — the fraction-stacked easy-part
    input conj(f)/f. Returns the result register in the same layout. The
    oracle for the Pallas kernel AND a self-check of the program against
    bn256_jax.pairing_is_one."""
    C = Consts(*(jnp.asarray(c) for c in _NP_CONSTS))
    x = jnp.moveaxis(nd, 1, -1)              # (2, 6, 2, 25, n)
    regs = [x] + [jnp.zeros_like(x) for _ in range(_N_REGS - 1)]
    for op, a, b, d in _build_program().tolist():
        regs = _apply_op(regs, op, a, b, d, C)
    return jnp.moveaxis(regs[_RESULT_REG], -1, 1)


# == the Pallas kernel =====================================================


def _kernel(prog_ref, nd_ref, *rest, n_steps: int):
    # rest = one ref per Consts field (in field order), out_ref, regs_ref
    nfields = len(Consts._fields)
    C = Consts(*(r[:] for r in rest[:nfields]))
    out_ref, regs_ref = rest[nfields], rest[nfields + 1]
    regs_ref[0] = _unpack(nd_ref[:])

    def body(step, carry):
        op = prog_ref[step, 0]
        a = prog_ref[step, 1]
        b = prog_ref[step, 2]
        d = prog_ref[step, 3]
        ra = regs_ref[a]

        @pl.when(op == 0)
        def _mul():
            regs_ref[d] = _fp12_mul(ra, regs_ref[b], C)

        @pl.when(op == 1)
        def _sw():
            regs_ref[d] = _swap(ra)

        @pl.when(op == 2)
        def _fr():
            regs_ref[d] = _frob(ra, b, C)

        @pl.when(op == 3)
        def _cp():
            regs_ref[d] = ra

        return carry

    lax.fori_loop(0, n_steps, body, 0)
    out_ref[:] = _pack(regs_ref[_RESULT_REG])


def _unpack(flat):
    """(2, 12, 25, B) -> (2, 6, 2, 25, B): split the plane axis (leading
    dims only — no minor-dim reshape, free in Mosaic)."""
    return flat.reshape((2, 6, 2) + flat.shape[-2:])


def _pack(x):
    return x.reshape((2, 12) + x.shape[-2:])


@functools.lru_cache(maxsize=8)
def _compiled(n_steps: int, interpret: bool):
    kernel = functools.partial(_kernel, n_steps=n_steps)

    @jax.jit
    def run(prog, nd):
        n = nd.shape[-1]
        grid = (n // BLOCK_LANES,)
        from jax.experimental.pallas import tpu as pltpu

        def whole(shape):
            rank = len(shape)
            return pl.BlockSpec(shape, lambda i, _r=rank: (0,) * _r)

        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((2, 12, KNL, BLOCK_LANES),
                             lambda i: (0, 0, 0, i)),
            ] + [whole(np.asarray(c).shape) for c in _NP_CONSTS],
            out_specs=pl.BlockSpec((2, 12, KNL, BLOCK_LANES),
                                   lambda i: (0, 0, 0, i)),
            out_shape=jax.ShapeDtypeStruct((2, 12, KNL, n), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((_N_REGS, 2, 6, 2, KNL, BLOCK_LANES),
                           jnp.int32)],
            interpret=interpret,
        )(prog, nd, *(jnp.asarray(c) for c in _NP_CONSTS))

    return run


def finalexp_is_one(f, *, interpret: bool = False):
    """Fraction-stacked final exponentiation == 1?, via the mega-kernel.

    f: (..., 6, 2, NL) int32 lazy limbs (ambient form, 22 or 25 wide) —
    the Miller-product to check, exactly `pairing_is_one`'s input.
    Returns bool (...,). Drop-in boolean twin of
    bn256_jax.pairing_is_one (the XLA easy-part stack and final
    canonical compare bracket the kernel)."""
    from gethsharding_tpu.ops import bn256_jax as k
    from gethsharding_tpu.ops.limb import NLIMBS

    lead = f.shape[:-3]
    nd = jnp.stack([k.fp12_conj(f), k.FP.normalize(f)])  # (2, ..., 6,2,NL)
    if NLIMBS < KNL:   # ambient exact form: widen losslessly
        nd = jnp.concatenate(
            [nd, jnp.zeros(nd.shape[:-1] + (KNL - NLIMBS,), jnp.int32)],
            axis=-1)
    n = 1
    for dim in lead:
        n *= dim
    nd = nd.reshape((2, n, 6, 2, KNL))
    ndT = jnp.moveaxis(nd, 1, -1)                       # (2, 6, 2, 25, n)
    ndT = ndT.reshape((2, 12, KNL, n))
    pad = (-n) % BLOCK_LANES
    if pad:
        ndT = jnp.concatenate(
            [ndT, jnp.zeros(ndT.shape[:-1] + (pad,), jnp.int32)], axis=-1)
    prog = jnp.asarray(_build_program())
    out = _compiled(int(prog.shape[0]), interpret)(prog, ndT)
    if pad:
        out = out[..., :n]
    out = jnp.moveaxis(out.reshape((2, 6, 2, KNL, n)), -1, 1)  # (2,n,6,2,25)
    # back to the ambient lazy form: one exact normalize per component
    # (handles the quasi-canonical -1 limbs; value < 2^LAZY_BITS)
    num = k.FP.normalize(out[0])
    den = k.FP.normalize(out[1])
    return k.fp12_eq(num, den).reshape(lead)


# == the Miller-loop mega-kernel ===========================================
# The other 21% of the dispatch (PERF.md stage shares): the 90-step
# shared-accumulator optimal-ate Miller product of the BLS committee
# check (`bn256_jax._bls_miller_opt`, projective flavor) as ONE
# pallas_call, same design as the final-exp kernel — an SMEM op stream
# (DBL / ADD(candidate)) drives a fori_loop whose body updates
# VMEM-resident (f, X, Y, Z) state; the per-step generator-line
# constants are a VMEM table indexed by step. Output is the
# fraction-stacked nd = conj(f)/f, i.e. exactly `finalexp_is_one`'s
# kernel input — the whole pairing check then runs in TWO kernel
# launches instead of ~600 XLA While dispatches.


def _fp2_add(x, y, C: Consts):
    return _normalize(x + y, C)


def _fp2_sub(x, y, C: Consts):
    return _normalize(x - y + C.negpad, C)


def _fp2_neg(x, C: Consts):
    return _normalize(C.negpad - x, C)


def _fp2_scalar(x, k: int, C: Consts):
    return _normalize(x * jnp.int32(k), C)


def _fp2_mul(x, y, C: Consts):
    """Full Fp2 product on row blocks: x, y (..., 2, 25, B).
    (a+bi)(c+di) = (ac - bd) + (ad + bc)i — one 4-plane conv."""
    a = x[..., 0:1, :, :]
    b = x[..., 1:2, :, :]
    c = y[..., 0:1, :, :]
    d = y[..., 1:2, :, :]
    u = jnp.concatenate([a, b, a, b], axis=-3)   # (..., 4, 25, B)
    v = jnp.concatenate([c, d, d, c], axis=-3)
    cols = _conv(u, v)                           # (..., 4, 49, B)
    rr = cols[..., 0, :, :] - cols[..., 1, :, :] + C.fp2pad[0]
    ii = cols[..., 2, :, :] + cols[..., 3, :, :]
    return _normalize(jnp.stack([rr, ii], axis=-3), C)


def _fp2_sqr(x, C: Consts):
    return _fp2_mul(x, x, C)


def _fp2_mul_fp(x, s, C: Consts):
    """Fp2 x (..., 2, 25, B) times Fp s (..., 25, B)."""
    cols = _conv(x, s[..., None, :, :])          # (..., 2, 49, B)
    return _normalize(cols, C)


def _fp2_conj_rows(x, C: Consts):
    a = x[..., 0, :, :]
    b = x[..., 1, :, :]
    return _normalize(jnp.stack([a, C.negpad - b], axis=-3), C)


# sparse line-mul tables (same derivation as bn256_jax._LINE_*)
_KLINE_POS = np.array([0, 1, 3])
_KLINE_J = np.array([[(k - d) % 6 for d in _KLINE_POS] for k in range(6)])
_KLINE_SEL = np.array([[0 if k - d >= 0 else 1 for d in _KLINE_POS]
                       for k in range(6)])
# line-mul group pad: group 0 accumulates terms A,B (re subtracts 2
# products), group 1 term C (re subtracts 1) — pad547 covers both
_LINE_PAD = np.zeros((2, 2, KNCOLS, 1), np.int32)  # (c, g, cols, 1)
_LINE_PAD[0, 0] = _rows(_PAD547, KNCOLS)
_LINE_PAD[0, 1] = _rows(_PAD547, KNCOLS)


def _fp12_mul_line(f, A, B, Cc, C: Consts):
    """f · (A + B·w + C·w³), sparse: 72 plane-pairs instead of 144.
    f (..., 6, 2, 25, B); A/B/Cc (..., 2, 25, B) Fp2 line terms."""
    xif = _mul_xi(f, C)
    src = (f, xif)
    lstack = jnp.stack([A, B, Cc], axis=-4)      # (..., 3t, 2, 25, B)
    op_rows = []
    for k in range(6):
        op_rows.append(jnp.stack(
            [src[_KLINE_SEL[k][t]][..., _KLINE_J[k][t], :, :, :]
             for t in range(3)], axis=-4))       # (..., 3t, 2, 25, B)
    op = jnp.stack(op_rows, axis=-5)             # (..., 6k, 3t, 2, 25, B)
    le = lstack[..., None, :, :, None, :, :]     # (..., 1, 3, 2a, 1, 25, B)
    ve = op[..., :, :, None, :, :, :]            # (..., 6, 3, 1, 2b, 25, B)
    cols = _conv(le, ve)                         # (..., 6, 3, 2, 2, 49, B)
    re = cols[..., 0, 0, :, :] - cols[..., 1, 1, :, :]  # (..., 6, 3, 49, B)
    im = cols[..., 0, 1, :, :] + cols[..., 1, 0, :, :]
    re_g = jnp.stack([re[..., 0, :, :] + re[..., 1, :, :],
                      re[..., 2, :, :]], axis=-3)       # (..., 6, 2g, 49, B)
    im_g = jnp.stack([im[..., 0, :, :] + im[..., 1, :, :],
                      im[..., 2, :, :]], axis=-3)
    acc = jnp.stack([re_g, im_g], axis=-4)       # (..., 6, 2c, 2g, 49, B)
    acc = acc + C.linepad
    parts = _normalize(acc, C)                   # (..., 6, 2, 2, 25, B)
    return _normalize(parts[..., 0, :, :] + parts[..., 1, :, :], C)


def _kernel_dbl_step(X, Y, Z, px, py, C: Consts):
    """Tangent step (bn256_jax._dbl_step, row layout). px/py Fp rows."""
    A = _fp2_sqr(X, C)
    Bq = _fp2_sqr(Y, C)
    Cq = _fp2_sqr(Bq, C)
    t = _fp2_sqr(_fp2_add(X, Bq, C), C)
    D = _fp2_scalar(_fp2_sub(_fp2_sub(t, A, C), Cq, C), 2, C)
    E = _fp2_scalar(A, 3, C)
    F = _fp2_sqr(E, C)
    X3 = _fp2_sub(F, _fp2_scalar(D, 2, C), C)
    Y3 = _fp2_sub(_fp2_mul(E, _fp2_sub(D, X3, C), C),
                  _fp2_scalar(Cq, 8, C), C)
    ZZ = _fp2_sqr(Z, C)
    Z3 = _fp2_scalar(_fp2_mul(Y, Z, C), 2, C)
    c_py = _fp2_mul(Z3, ZZ, C)
    c_px = _fp2_neg(_fp2_mul(E, ZZ, C), C)
    c_const = _fp2_sub(_fp2_mul(E, X, C), _fp2_scalar(Bq, 2, C), C)
    line = (_fp2_mul_fp(c_py, py, C), _fp2_mul_fp(c_px, px, C), c_const)
    return line, X3, Y3, Z3


def _kernel_jadd_step(X1, Y1, Z1, cand, px, py, C: Consts):
    """Full Jacobian chord step (bn256_jax._jadd_step, row layout).
    cand = (x2, y2, z2, zz2, zzz2) each (..., 2, 25, B)."""
    x2, y2, z2, zz2, zzz2 = cand
    Z1Z1 = _fp2_sqr(Z1, C)
    U1 = _fp2_mul(X1, zz2, C)
    U2 = _fp2_mul(x2, Z1Z1, C)
    S1 = _fp2_mul(Y1, zzz2, C)
    S2 = _fp2_mul(y2, _fp2_mul(Z1, Z1Z1, C), C)
    H = _fp2_sub(U2, U1, C)
    R = _fp2_sub(S2, S1, C)
    HH = _fp2_sqr(H, C)
    V = _fp2_mul(U1, HH, C)
    HHH = _fp2_mul(H, HH, C)
    X3 = _fp2_sub(_fp2_sub(_fp2_sqr(R, C), HHH, C),
                  _fp2_scalar(V, 2, C), C)
    Y3 = _fp2_sub(_fp2_mul(R, _fp2_sub(V, X3, C), C),
                  _fp2_mul(S1, HHH, C), C)
    Z3 = _fp2_mul(_fp2_mul(Z1, z2, C), H, C)
    c_const = _fp2_sub(_fp2_mul(_fp2_mul(X1, y2, C), Z1, C),
                       _fp2_mul(_fp2_mul(x2, Y1, C), z2, C), C)
    line = (_fp2_mul_fp(Z3, py, C), _fp2_mul_fp(_fp2_neg(R, C), px, C),
            c_const)
    return line, X3, Y3, Z3


_ONE12 = np.zeros((6, 2, KNL, 1), np.int32)
_ONE12[0, 0, 0, 0] = 1


def _miller_tables():
    """(ops, gen_lines, twf): the static optimal-ate schedule, its
    generator-line constants and the twist-Frobenius constants, all at
    kernel width (ambient tables zero-pad losslessly from 22 limbs)."""
    from gethsharding_tpu.ops import bn256_jax as k

    def widen(arr):
        arr = np.asarray(arr, np.int32)
        if arr.shape[-1] < KNL:
            arr = np.concatenate(
                [arr, np.zeros(arr.shape[:-1] + (KNL - arr.shape[-1],),
                               np.int32)], axis=-1)
        return arr

    ops = np.asarray(k._OPT_OPS, np.int32)
    lines = widen(k._GEN_LINES)                       # (L, 3, 2, 25)
    twf = np.stack([widen(k._TWF_X), widen(k._TWF_Y),
                    widen(k._TWF2_X), widen(k._TWF2_Y)])  # (4, 2, 25)
    return ops, lines, twf


def _miller_body(state, op, line_c, ctx, C: Consts):
    """One optimal-ate step on (f, X, Y, Z) — shared verbatim by the
    XLA oracle (static op) and the kernel's pl.when branches."""
    f, X, Y, Z = state
    sx, sy, sz, hx, hy_neg, cand = ctx
    gen = (_fp2_mul_fp(line_c[0], sy, C),
           _fp2_mul_fp(line_c[1], sx, C),
           _fp2_mul_fp(line_c[2], sz, C))
    if op == 0:
        line1, X, Y, Z = _kernel_dbl_step(X, Y, Z, hx, hy_neg, C)
        f = _fp12_mul(f, f, C)
    else:
        line1, X, Y, Z = _kernel_jadd_step(
            X, Y, Z, tuple(cand[op - 1][k] for k in range(5)),
            hx, hy_neg, C)
    f = _fp12_mul_line(f, *gen, C)
    f = _fp12_mul_line(f, *line1, C)
    return f, X, Y, Z


def _miller_candidates(pkx, pky, pkz, twf, C: Consts):
    """The four Jacobian add candidates [+Q, -Q, piQ, -pi^2 Q] with
    their z-power precomputes (bn256_jax._bls_miller_opt preamble)."""
    q1x = _fp2_mul(_fp2_conj_rows(pkx, C), twf[0], C)
    q1y = _fp2_mul(_fp2_conj_rows(pky, C), twf[1], C)
    q2x = _fp2_mul(pkx, twf[2], C)
    q2ny = _fp2_neg(_fp2_mul(pky, twf[3], C), C)
    zconj = _fp2_conj_rows(pkz, C)
    cands = []
    for cx, cy, cz in ((pkx, pky, pkz),
                       (pkx, _fp2_neg(pky, C), pkz),
                       (q1x, q1y, zconj),
                       (q2x, q2ny, pkz)):
        zz = _fp2_sqr(cz, C)
        cands.append((_fp2_mul(cx, cz, C), _fp2_mul(cy, zz, C),
                      _normalize(cz, C), zz, _fp2_mul(cz, zz, C)))
    return cands


def run_miller_xla(sig, h, pk):
    """The full Miller program as plain XLA ops — the kernel's oracle.

    sig = (sx, sy, sz) each (n, 25); h = (hx, hy) each (n, 25);
    pk = (pkx, pky, pkz) each (n, 2, 25): kernel-width limbs. Returns
    f (n, 6, 2, 25)."""
    C = Consts(*(jnp.asarray(c) for c in _NP_CONSTS))
    ops, lines, twf = _miller_tables()
    sx, sy, sz = (jnp.moveaxis(v, 0, -1) for v in sig)      # (25, n)
    hx, hy = (jnp.moveaxis(v, 0, -1) for v in h)
    pkx, pky, pkz = (jnp.moveaxis(v, 0, -1) for v in pk)    # (2, 25, n)
    hy_neg = _normalize(C.negpad - hy, C)
    cand = _miller_candidates(pkx, pky, pkz,
                              jnp.asarray(twf)[..., None], C)
    n = sx.shape[-1]
    f = jnp.broadcast_to(C.one12, (6, 2, KNL, n)).astype(jnp.int32)
    X = _fp2_mul(pkx, pkz, C)
    Y = _fp2_mul(pky, _fp2_sqr(pkz, C), C)
    Z = _normalize(pkz, C)
    ctx = (sx, sy, sz, hx, hy_neg, cand)
    state = (f, X, Y, Z)
    for i, op in enumerate(ops.tolist()):
        line_c = jnp.asarray(lines[i])[..., None]           # (3, 2, 25, 1)
        state = _miller_body(state, op, line_c, ctx, C)
    return jnp.moveaxis(state[0], -1, 0)                    # (n, 6, 2, 25)


# resolved at module end: every const table above must exist first
_NP_CONSTS = _np_consts()


def _miller_kernel(ops_ref, lines_ref, sx_ref, sy_ref, sz_ref, hx_ref,
                   hy_ref, pkx_ref, pky_ref, pkz_ref, twf_ref,
                   c_fold, c_lift, c_mulpad, c_fp2pad, c_negpad, c_gamma,
                   c_linepad, c_one12, out_ref,
                   f_ref, X_ref, Y_ref, Z_ref, cand_ref, *, n_steps: int):
    C = Consts(fold_t=c_fold[:], lift=c_lift[:], mulpad=c_mulpad[:],
               fp2pad=c_fp2pad[:], negpad=c_negpad[:], gamma=c_gamma[:],
               linepad=c_linepad[:], one12=c_one12[:])
    sx = sx_ref[:]
    sy = sy_ref[:]
    sz = sz_ref[:]
    hx = hx_ref[:]
    hy_neg = _normalize(C.negpad - hy_ref[:], C)
    pkx = pkx_ref[:]
    pky = pky_ref[:]
    pkz = pkz_ref[:]
    twf = twf_ref[:][..., None]                   # (4, 2, 25, 1)

    for idx, comp in enumerate(
            _miller_candidates(pkx, pky, pkz, twf, C)):
        cand_ref[idx] = jnp.stack(comp, axis=0)   # (5, 2, 25, B)
    lanes = sx.shape[-1]
    f_ref[:] = jnp.broadcast_to(C.one12,
                                (6, 2, KNL, lanes)).astype(jnp.int32)
    X_ref[:] = _fp2_mul(pkx, pkz, C)
    Y_ref[:] = _fp2_mul(pky, _fp2_sqr(pkz, C), C)
    Z_ref[:] = _normalize(pkz, C)

    def body(step, carry):
        op = ops_ref[step]
        line_c = lines_ref[step][..., None]       # (3, 2, 25, 1)
        gen = (_fp2_mul_fp(line_c[0], sy, C),
               _fp2_mul_fp(line_c[1], sx, C),
               _fp2_mul_fp(line_c[2], sz, C))

        @pl.when(op == 0)
        def _dbl():
            line1, X3, Y3, Z3 = _kernel_dbl_step(
                X_ref[:], Y_ref[:], Z_ref[:], hx, hy_neg, C)
            f = _fp12_mul(f_ref[:], f_ref[:], C)
            f = _fp12_mul_line(f, *gen, C)
            f_ref[:] = _fp12_mul_line(f, *line1, C)
            X_ref[:] = X3
            Y_ref[:] = Y3
            Z_ref[:] = Z3

        @pl.when(op != 0)
        def _add():
            cd = cand_ref[op - 1]                 # (5, 2, 25, B)
            line1, X3, Y3, Z3 = _kernel_jadd_step(
                X_ref[:], Y_ref[:], Z_ref[:],
                tuple(cd[i] for i in range(5)), hx, hy_neg, C)
            f = _fp12_mul_line(f_ref[:], *gen, C)
            f_ref[:] = _fp12_mul_line(f, *line1, C)
            X_ref[:] = X3
            Y_ref[:] = Y3
            Z_ref[:] = Z3

        return carry

    lax.fori_loop(0, n_steps, body, 0)
    f = f_ref[:]
    out_ref[:] = f.reshape((12,) + f.shape[-2:])  # (12, 25, B)


@functools.lru_cache(maxsize=8)
def _miller_compiled(n_steps: int, interpret: bool):
    kernel = functools.partial(_miller_kernel, n_steps=n_steps)

    @jax.jit
    def run(ops, lines, sx, sy, sz, hx, hy, pkx, pky, pkz, twf):
        n = sx.shape[-1]
        grid = (n // BLOCK_LANES,)
        from jax.experimental.pallas import tpu as pltpu

        def whole(shape):
            rank = len(shape)
            return pl.BlockSpec(shape, lambda i, _r=rank: (0,) * _r)

        def fp_spec():
            return pl.BlockSpec((KNL, BLOCK_LANES), lambda i: (0, i))

        def fp2_spec():
            return pl.BlockSpec((2, KNL, BLOCK_LANES), lambda i: (0, 0, i))

        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),    # ops
                whole(lines.shape),
                fp_spec(), fp_spec(), fp_spec(),           # sig
                fp_spec(), fp_spec(),                      # h
                fp2_spec(), fp2_spec(), fp2_spec(),        # pk
                whole(twf.shape),
            ] + [whole(np.asarray(c).shape) for c in _NP_CONSTS],
            out_specs=pl.BlockSpec((12, KNL, BLOCK_LANES),
                                   lambda i: (0, 0, i)),
            out_shape=jax.ShapeDtypeStruct((12, KNL, n), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((6, 2, KNL, BLOCK_LANES), jnp.int32),
                pltpu.VMEM((2, KNL, BLOCK_LANES), jnp.int32),
                pltpu.VMEM((2, KNL, BLOCK_LANES), jnp.int32),
                pltpu.VMEM((2, KNL, BLOCK_LANES), jnp.int32),
                pltpu.VMEM((4, 5, 2, KNL, BLOCK_LANES), jnp.int32),
            ],
            interpret=interpret,
        )(ops, lines, sx, sy, sz, hx, hy, pkx, pky, pkz, twf,
          *(jnp.asarray(c) for c in _NP_CONSTS))

    return run


def miller_f(sig, hx, hy, pk, *, interpret: bool = False):
    """Projective shared-accumulator Miller product via the mega-kernel.

    Drop-in for `bn256_jax._bls_miller_opt`'s projective flavor: sig =
    (sx, sy, sz) (..., NL) Fp limbs, hx/hy (..., NL), pk = (pkx, pky,
    pkz) (..., 2, NL) Fp2 limbs — ambient form in, ambient lazy form
    out (..., 6, 2, NL). The ~90-step walk runs as ONE kernel launch."""
    from gethsharding_tpu.ops import bn256_jax as k

    ops, lines, twf = _miller_tables()
    lead = sig[0].shape[:-1]
    n = 1
    for dim in lead:
        n *= dim

    def prep(v, fp2: bool):
        v = v.reshape((n,) + v.shape[len(lead):])
        if v.shape[-1] < KNL:
            v = jnp.concatenate(
                [v, jnp.zeros(v.shape[:-1] + (KNL - v.shape[-1],),
                              jnp.int32)], axis=-1)
        v = jnp.moveaxis(v, 0, -1)                 # (25, n) | (2, 25, n)
        pad = (-n) % BLOCK_LANES
        if pad:
            v = jnp.concatenate(
                [v, jnp.zeros(v.shape[:-1] + (pad,), jnp.int32)], axis=-1)
        return v

    args = ([prep(v, False) for v in sig]
            + [prep(hx, False), prep(hy, False)]
            + [prep(v, True) for v in pk])
    out = _miller_compiled(int(ops.shape[0]), interpret)(
        jnp.asarray(ops), jnp.asarray(lines), *args, jnp.asarray(twf))
    if (-n) % BLOCK_LANES:
        out = out[..., :n]
    f = jnp.moveaxis(out.reshape((6, 2, KNL, n)), -1, 0)
    f = f.reshape(lead + (6, 2, KNL))
    # back to the ambient lazy form (exact-width callers fold 25 -> 22)
    return k.FP.normalize(f)


# == the aggregation mega-kernels ==========================================
# The remaining 10% of the dispatch: the masked projective tree sums of
# committee signatures (G1) and voter pubkeys (G2). Same complete RCB16
# addition formulas as bn256_jax._proj_add_impl, with the committee tree
# as a STATIC 8-level loop inside one kernel — each level's adds process
# every surviving pair in full-tile ops, so the whole 135-slot committee
# reduction is ONE launch per group instead of ~25 XLA dispatch levels.
# With GETHSHARDING_TPU_AGG=mega beside the Miller and final-exp
# kernels, the audit dispatch is 4 launches.

AGG_LANES = 64  # smaller lane block: level-0 conv temporaries dominate VMEM
# The in-kernel tree is fully unrolled, so its VMEM stack, its Mosaic
# lowering and its compile time all grow with the committee width it
# reduces: at the audit's 144 slots (padded to 256) the G1 kernel asked
# for 29.7 MB of scoped VMEM against Mosaic's 16 MB and took ~10 minutes
# to say so (v5e, PR 21). The kernel therefore reduces chunks of
# AGG_CHUNK slots (a second grid axis; wider committees pad up to a
# chunk multiple, and 8 divides the audit's 144) and the chunk sums
# fold through the XLA complete-addition tree.
AGG_CHUNK = 8


def _fp_mul_rows(x, y, C: Consts):
    """Fp product on (..., 25, B) rows: 1-plane conv + normalize."""
    return _normalize(_conv(x, y), C)


def _fp_sub_rows(x, y, C: Consts):
    return _normalize(x - y + C.negpad, C)


def _agg_tree(px, py, pz, C: Consts, *, fp2: bool, b3):
    """(2^k, ...) point stacks -> the projective sum, RCB16 complete
    adds (a=0), halving per level. b3: int 9 for G1, Fp2 rows for G2."""
    if fp2:
        mul = lambda a, b: _fp2_mul(a, b, C)
        add = lambda a, b: _fp2_add(a, b, C)
        sub = lambda a, b: _fp2_sub(a, b, C)
        mul_b3 = lambda v: _fp2_mul(v, b3, C)
    else:
        mul = lambda a, b: _fp_mul_rows(a, b, C)
        add = lambda a, b: _normalize(a + b, C)
        sub = lambda a, b: _fp_sub_rows(a, b, C)
        mul_b3 = lambda v: _normalize(v * jnp.int32(b3), C)

    def proj_add(p1, p2):
        x1, y1, z1 = p1
        x2, y2, z2 = p2
        t0 = mul(x1, x2)
        t1 = mul(y1, y2)
        t2 = mul(z1, z2)
        t3 = sub(mul(add(x1, y1), add(x2, y2)), add(t0, t1))
        t4 = sub(mul(add(y1, z1), add(y2, z2)), add(t1, t2))
        t5 = sub(mul(add(x1, z1), add(x2, z2)), add(t0, t2))
        t0 = add(add(t0, t0), t0)
        t2 = mul_b3(t2)
        zs = add(t1, t2)
        t1 = sub(t1, t2)
        yb = mul_b3(t5)
        return (sub(mul(t3, t1), mul(t4, yb)),
                add(mul(t1, zs), mul(t0, yb)),
                add(mul(zs, t4), mul(t0, t3)))

    while px.shape[0] > 1:
        half = px.shape[0] // 2
        px, py, pz = proj_add(
            (px[:half], py[:half], pz[:half]),
            (px[half:], py[half:], pz[half:]))
    return px[0], py[0], pz[0]


def _agg_kernel(xs_ref, ys_ref, mask_ref, b3_ref,
                c_fold, c_lift, c_mulpad, c_fp2pad, c_negpad, c_gamma,
                c_linepad, c_one12, ox_ref, oy_ref, oz_ref,
                *, fp2: bool, g1_b3: int):
    C = Consts(fold_t=c_fold[:], lift=c_lift[:], mulpad=c_mulpad[:],
               fp2pad=c_fp2pad[:], negpad=c_negpad[:], gamma=c_gamma[:],
               linepad=c_linepad[:], one12=c_one12[:])
    # data refs carry two leading size-1 axes (the grid axes): the lane
    # group and the committee chunk. Mosaic requires a block's LANE dim
    # to be 128-divisible or equal the array's, so lanes are pre-split
    # host-side into (groups, 64) and the grid walks groups (block 64
    # over a 128-lane array is rejected)
    xs = xs_ref[0, 0]                  # (Cp, [2,] 25, B)
    ys = ys_ref[0, 0]
    m = mask_ref[0, 0]                 # (Cp, 1, B) | (Cp, 1, 1, B)
    one_limb = (C.one12[0] if fp2 else C.one12[0, 0])  # (2,25,1)|(25,1)
    one = jnp.broadcast_to(one_limb, xs.shape[1:]).astype(jnp.int32)
    px = jnp.where(m != 0, xs, 0)
    py = jnp.where(m != 0, ys, one)
    pz = jnp.where(m != 0, one, jnp.zeros_like(one))
    b3 = b3_ref[:] if fp2 else g1_b3
    X, Y, Z = _agg_tree(px, py, pz, C, fp2=fp2, b3=b3)
    ox_ref[0, 0] = X
    oy_ref[0, 0] = Y
    oz_ref[0, 0] = Z


@functools.lru_cache(maxsize=16)
def _agg_compiled(cp: int, fp2: bool, interpret: bool):
    from gethsharding_tpu.ops import bn256_jax as k

    g1_b3 = 9  # 3*b on y^2 = x^3 + 3
    b3g2 = np.zeros((2, KNL, 1), np.int32)
    src = np.asarray(k._B3_G2_LIMBS, np.int32)
    b3g2[:, : src.shape[-1], 0] = src
    kernel = functools.partial(_agg_kernel, fp2=fp2, g1_b3=g1_b3)
    point_shape = (cp, 2, KNL) if fp2 else (cp, KNL)
    mask_shape = (cp, 1, 1) if fp2 else (cp, 1)
    out_shape = (2, KNL) if fp2 else (KNL,)

    @jax.jit
    def run(xs, ys, mask):
        # data arrays arrive as (groups, chunks, ..., AGG_LANES): the
        # lane axis is pre-split so each block's lane dim EQUALS the
        # array's (the Mosaic block-shape rule), and the grid walks the
        # lane groups and the committee chunks
        g, nc = xs.shape[:2]
        grid = (g, nc)

        def whole(shape):
            rank = len(shape)
            return pl.BlockSpec(shape, lambda i, j, _r=rank: (0,) * _r)

        def data(shape):
            rank = len(shape) + 3
            return pl.BlockSpec(
                (1, 1) + shape + (AGG_LANES,),
                lambda i, j, _r=rank: (i, j) + (0,) * (_r - 2))

        out_specs = [data(out_shape)] * 3
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[data(point_shape), data(point_shape),
                      data(mask_shape), whole(b3g2.shape)]
            + [whole(np.asarray(c).shape) for c in _NP_CONSTS],
            out_specs=out_specs,
            out_shape=[jax.ShapeDtypeStruct(
                (g, nc) + out_shape + (AGG_LANES,), jnp.int32)] * 3,
            interpret=interpret,
        )(xs, ys, mask, jnp.asarray(b3g2),
          *(jnp.asarray(c) for c in _NP_CONSTS))

    return run


def aggregate_proj(xs, ys, mask, *, fp2: bool, interpret: bool = False):
    """Masked committee sum via the tree mega-kernel (ambient in/out).

    xs/ys: (..., C, NL) G1 or (..., C, 2, NL) G2 affine limbs;
    mask (..., C) bool. Returns projective (X, Y, Z)."""
    from gethsharding_tpu.ops import bn256_jax as k

    point_rank = 3 if fp2 else 2
    lead = xs.shape[:-point_rank]
    cdim = xs.shape[len(lead)]
    if cdim <= AGG_CHUNK:
        cp = 1 << max(1, (cdim - 1).bit_length())   # one pow2 chunk
        nc = 1
    else:
        cp = AGG_CHUNK
        nc = -(-cdim // cp)
    n = 1
    for dim in lead:
        n *= dim

    def prep(v, widen: bool):
        v = v.reshape((n,) + v.shape[len(lead):])
        if widen and v.shape[-1] < KNL:
            v = jnp.concatenate(
                [v, jnp.zeros(v.shape[:-1] + (KNL - v.shape[-1],),
                              v.dtype)], axis=-1)
        pad_c = nc * cp - cdim
        if pad_c:
            v = jnp.concatenate(
                [v, jnp.zeros((n, pad_c) + v.shape[2:], v.dtype)], axis=1)
        v = v.reshape((n, nc, cp) + v.shape[2:])
        v = jnp.moveaxis(v, 0, -1)              # (nc, Cp, ..., n)
        pad = (-n) % AGG_LANES
        if pad:
            v = jnp.concatenate(
                [v, jnp.zeros(v.shape[:-1] + (pad,), v.dtype)], axis=-1)
        # split lanes into (groups, AGG_LANES) and lead with the group
        # axis: each pallas block's lane dim then EQUALS its array's
        # lane dim (Mosaic's block-shape rule; see _agg_compiled)
        groups = v.shape[-1] // AGG_LANES
        v = v.reshape(v.shape[:-1] + (groups, AGG_LANES))
        return jnp.moveaxis(v, -2, 0)           # (g, nc, Cp, ..., 64)

    xs_t = prep(jnp.asarray(xs), True)
    ys_t = prep(jnp.asarray(ys), True)
    m = mask[..., None, None] if fp2 else mask[..., None]
    m_t = prep(jnp.asarray(m, jnp.int32), False)
    out = _agg_compiled(cp, fp2, interpret)(xs_t, ys_t, m_t)
    res = []
    for v in out:                               # (g, nc, out..., 64)
        v = jnp.moveaxis(v, 0, -2)              # (nc, out..., g, 64)
        v = v.reshape(v.shape[:-2] + (v.shape[-2] * AGG_LANES,))
        if (-n) % AGG_LANES:
            v = v[..., :n]
        v = jnp.moveaxis(v, -1, 0)              # (n, nc, out...)
        v = v.reshape(lead + v.shape[1:])
        res.append(k.FP.normalize(v))
    # fold the chunk sums (axis right after the batch dims) through the
    # XLA complete-addition tree; one chunk squeezes straight through
    return k._tree_reduce(tuple(res), -point_rank,
                          k._g2_proj_add if fp2 else k._g1_proj_add)
