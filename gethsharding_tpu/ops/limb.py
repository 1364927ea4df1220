"""Batched 256-bit modular arithmetic on TPU: 12-bit limb planes in int32.

This is the foundation under the bn256 pairing and secp256k1 kernels
(SURVEY.md §7 hard part 1: "big-integer modular arithmetic on TPU — needs
limb decomposition to run inside MXU/VPU efficiently"). Design:

- A field element is 25 limbs x 12 bits stored little-endian in int32,
  shape ``(..., 25)``. The leading axes are the batch — every op is
  batch-first and jit/vmap/shard_map-safe (static shapes, no 64-bit dtypes,
  no data-dependent control flow).
- Products of 12-bit limbs are 24 bits; a schoolbook column accumulates at
  most 25 of them, and fused callers sum up to FOUR such products:
  4 * 25 * (2^12-1)^2 < 2^30.7, safely inside int32. No Montgomery form:
  reduction folds limbs >= FOLD_BASE(=22) through a precomputed
  ``(2^(12*(22+k)) mod p)`` matrix — a small integer matmul, the natural
  TPU shape — followed by ONE exact carry propagation.
- Elements are kept *lazily* reduced: canonical limbs (< 2^12), width 25,
  value in [0, 2^LAZY_BITS), congruent mod p. The width is 3 limbs wider
  than the fold base ON PURPOSE: it lets `normalize` finish with a single
  exact carry (the serialized lax.scan that dominates kernel latency on
  TPU) instead of the three an exact 22-limb form needs — the overflow
  above 2^264 simply stays in the top limbs until the next fold. `canon`
  produces the unique value < p for equality/export.

The reference's equivalents are hand-written Montgomery assembly
(`crypto/bn256/cloudflare/gfp_amd64.s`: gfpNeg/Add/Sub/Mul) and C field
code (`crypto/secp256k1/libsecp256k1`); those are scalar-serial designs.
This one trades per-element latency for batch throughput, which is what the
135-vote x 100-shard workload (BASELINE.md) actually needs.
"""

from __future__ import annotations

import os
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

LIMB_BITS = 12
LIMB_MASK = (1 << LIMB_BITS) - 1

# Two lazy representations, selected by $GETHSHARDING_TPU_LIMB_FORM:
# - "wide" (default): 25-limb operands, value < 2^273, ONE exact carry per
#   normalize — minimizes sequential depth (TPU latency).
# - "exact": 22-limb operands, value < 2^264, three exact carries per
#   normalize — minimizes schoolbook width (+29% fewer product FLOPs);
#   the form the Pallas kernels (ops/pallas_finalexp.py) were first
#   measured beside (PERF.md section 6, PR 30). The kernels compute in
#   25 limbs whatever the form, so it moves only the XLA work left.
LIMB_FORM = os.environ.get("GETHSHARDING_TPU_LIMB_FORM", "wide")
if LIMB_FORM == "wide":
    NLIMBS = 25    # operand width: 300 bits of capacity
    LAZY_BITS = 273  # lazy-form value bound (see normalize)
elif LIMB_FORM == "exact":
    NLIMBS = 22
    LAZY_BITS = 264
else:
    raise ValueError(
        f"GETHSHARDING_TPU_LIMB_FORM must be 'wide' or 'exact', got {LIMB_FORM!r}")
FOLD_BASE = 22     # limbs >= FOLD_BASE fold back under the modulus
FOLD_ROWS = 33     # max high limbs a single fold can absorb
RADIX = 1 << (LIMB_BITS * NLIMBS)


def int_to_limbs(value: int, nlimbs: int = NLIMBS) -> np.ndarray:
    """Little-endian 12-bit limb decomposition of a non-negative int."""
    if value < 0:
        raise ValueError("negative value")
    limbs = np.zeros(nlimbs, dtype=np.int32)
    for i in range(nlimbs):
        limbs[i] = value & LIMB_MASK
        value >>= LIMB_BITS
    if value:
        raise ValueError("value does not fit in limbs")
    return limbs


def limbs_to_int(limbs) -> int:
    """Inverse of int_to_limbs (host-side; accepts any int dtype array)."""
    arr = np.asarray(limbs)
    return sum(int(arr[..., i].item()) << (LIMB_BITS * i) for i in range(arr.shape[-1])) \
        if arr.ndim == 1 else _limbs_to_int_nd(arr)


def _limbs_to_int_nd(arr: np.ndarray):
    out = np.zeros(arr.shape[:-1], dtype=object)
    for i in range(arr.shape[-1]):
        out = out + (arr[..., i].astype(object) << (LIMB_BITS * i))
    return out


_BLOCK_ROWS = 4096  # rows of one bit-plane pass (`bytes_to_limbs`)


def ints_to_bytes(values: Sequence[int],
                  nlimbs: int = NLIMBS) -> np.ndarray:
    """The integer entry of `ints_to_limbs`: (batch,) python ints ->
    (batch, nbytes) uint8, little-endian, `nbytes` the whole bytes
    `nlimbs` limbs take: one to_bytes per int (C speed). Read-only."""
    nbytes = -(-nlimbs * LIMB_BITS // 8)
    try:
        raw = b"".join(v.to_bytes(nbytes, "little") for v in values)
    except OverflowError as exc:
        raise ValueError(f"value out of range for {nlimbs} limbs") from exc
    return np.frombuffer(raw, np.uint8).reshape(len(values), nbytes)


def ints_to_limbs(values: Sequence[int], nlimbs: int = NLIMBS,
                  out_dtype=np.int32) -> np.ndarray:
    """Batch conversion: (batch,) python ints -> (batch, nlimbs) limbs.

    Two steps, `ints_to_bytes` then `bytes_to_limbs`: a caller that
    holds bytes already (a packed row off the wire) enters at the
    second. This sits on the host marshalling critical path
    (hashes/signatures -> limbs for every batch dispatch)."""
    return bytes_to_limbs(ints_to_bytes(values, nlimbs), nlimbs, out_dtype)


def bytes_to_limbs(arr: np.ndarray, nlimbs: int = NLIMBS,
                   out_dtype=np.int32) -> np.ndarray:
    """(batch, nbytes) uint8 little-endian -> (batch, nlimbs) limbs, a
    numpy bit-plane extraction. `out_dtype` lets the u16 wire format
    (12-bit limbs always fit uint16) marshal straight into the wire
    width instead of paying a second full-plane astype copy of the
    audit's largest buffers."""
    n, nbytes = arr.shape
    if n == 0:
        return np.zeros((0, nlimbs), out_dtype)
    spare_bits = nbytes * 8 - nlimbs * LIMB_BITS
    if spare_bits:
        # capacity is not byte-aligned: the spare top bits must be zero
        # (vectorized — a python loop here costs more than the whole
        # bit-plane extraction at audit batch sizes)
        if (arr[:, -1] >> (8 - spare_bits)).any():
            raise ValueError("value does not fit in limbs")
    # limb pairs span 3 bytes: even = b0 | low-nibble(b1)<<8, odd =
    # high-nibble(b1) | b2<<4. Contiguous reshape + strided writes beat
    # the per-limb gather by ~6x on the audit marshalling path. In
    # blocks of rows: one pass over a whole committee plane (64,512
    # rows into 6.4 MB of int32) took 14-23 ms in a process that runs
    # the TPU runtime's 160 threads, blocks of 2,048-16,384 rows 4.6-5.3
    # ms for the same plane (PERF.md section 6, PR 36).
    pairs = nlimbs // 2
    out = np.empty((n, nlimbs), out_dtype)
    for lo in range(0, n, _BLOCK_ROWS):
        a, o = arr[lo:lo + _BLOCK_ROWS], out[lo:lo + _BLOCK_ROWS]
        if pairs:
            main = a[:, :pairs * 3].reshape(len(a), pairs, 3).astype(
                np.uint16)
            o[:, 0:2 * pairs:2] = main[..., 0] | ((main[..., 1] & 0x0F) << 8)
            o[:, 1:2 * pairs:2] = (main[..., 1] >> 4) | (main[..., 2] << 4)
        if nlimbs % 2:
            # trailing even limb: its 12 bits start at byte 3*pairs
            b0 = pairs * 3
            tail = a[:, b0].astype(np.int32)
            if b0 + 1 < nbytes:
                tail |= (a[:, b0 + 1].astype(np.int32) & 0x0F) << 8
            o[:, -1] = tail
    return out


def _relaxed_round(z: jnp.ndarray):
    """One vectorized carry round: z_i -> (z_i & mask) + carry(z_{i-1}).

    Width-preserving; returns (top_carry, z'). Shrinks limb magnitude by
    ~2^LIMB_BITS per round (4 cheap elementwise ops, no sequential loop).
    """
    lo = z & LIMB_MASK
    c = z >> LIMB_BITS  # arithmetic shift: negative carries = borrows
    shifted = jnp.concatenate(
        [jnp.zeros_like(c[..., :1]), c[..., :-1]], axis=-1)
    return c[..., -1], lo + shifted


def conv_cols(prod: jnp.ndarray) -> jnp.ndarray:
    """Anti-diagonal column sums: (..., L, M) -> (..., L+M-1) with
    out[n] = sum over l of prod[l, n-l] (0 <= n-l < M).

    The building block of every limb product. Row l of the product
    belongs to columns l .. l+M-1: it is padded with l zeros below and
    L-1-l above, and the L rows are added. The pads are STATIC, so XLA
    fuses slice, pad and add into one pass over the product. Not the
    shorter re-viewing form (pad each row with L zeros, flatten, re-view
    at width M+L-1, sum rows): its two reshapes change the minor
    dimension of a tiled array, which on the chip re-lays every word of
    the padded product through HBM. PERF.md section 6 (PR 29) has the
    chip's ranking of this form against the ones it replaced."""
    L = prod.shape[-2]
    # lax.pad and not jnp.pad: a pairing kernel traces ~10^3 products,
    # and jnp.pad's Python made that a quarter slower (33.9 s against
    # 26.7 on XLA:CPU).
    zero = jnp.zeros((), prod.dtype)
    lead = [(0, 0, 0)] * (prod.ndim - 2)
    out = None
    for l in range(L):
        row = lax.pad(lax.index_in_dim(prod, l, prod.ndim - 2, False),
                      zero, lead + [(l, L - 1 - l, 0)])
        out = row if out is None else out + row
    return out


def _carry_scan(z: jnp.ndarray):
    """Exact carry propagation along the last axis, as a sequential
    `lax.scan` (a compact graph: the big pairing kernels trace thousands
    of these).

    Accepts limbs of either sign with magnitude < 2^31 (arithmetic >> gives
    floor division, so borrows propagate as negative carries). Returns
    (carry_out, limbs): total carry off the top (callers either know it is
    zero or use its sign as a borrow flag) and canonical limbs.
    """
    zs = jnp.moveaxis(z, -1, 0)

    def step(c, x):
        t = x + c
        return t >> LIMB_BITS, t & LIMB_MASK

    # init carry derived from the input so its varying-manual-axes
    # match under shard_map (a fresh constant would be unvarying)
    carry, out = lax.scan(step, zs[0] * 0, zs)
    return carry, jnp.moveaxis(out, 0, -1)


def _pallas_wanted() -> bool:
    """Do the Pallas kernels run in this process? On every platform but
    the CPU (whose interpreter path is for tests), yes — and a failure
    to resolve the backend or to compile the kernel propagates: a
    program that chose a kernel never gets the XLA path in its place."""
    return jax.default_backend() != "cpu"


def _carry(z: jnp.ndarray) -> jnp.ndarray:
    """Full carry propagation; the final carry out is dropped (asserted zero
    by the differential tests, not at runtime — runtime checks would break
    jit). The caller must guarantee the value is non-negative and fits."""
    return _carry_scan(z)[1]


class ModArith:
    """Batched arithmetic mod a fixed prime p < 2^255 (constants baked in).

    One instance per modulus; all methods are pure functions of jnp arrays
    and close over numpy constants, so they trace cleanly under jit, vmap,
    pjit and shard_map.
    """

    def __init__(self, p: int):
        # Lazy-form headroom: values live in [0, 2^LAZY_BITS); the bound
        # derivation in `normalize` holds for any p < 2^257 (covers the
        # 254-bit bn256 and 256-bit secp256k1 fields).
        if p.bit_length() > 256:
            raise ValueError("modulus too large for the lazy limb form")
        self.p = p
        # Fold matrix: row k holds limbs of 2^(12*(FOLD_BASE+k)) mod p.
        # FOLD_ROWS rows cover the widest intermediate (fused accumulators
        # reach 49 columns, + 3 relaxed-round pad limbs -> 30 high limbs).
        self.fold_j = np.stack(
            [int_to_limbs(pow(1 << (LIMB_BITS * (FOLD_BASE + k)), 1, p),
                          FOLD_BASE)
             for k in range(FOLD_ROWS)]
        )  # (FOLD_ROWS, 22) int32; numpy on purpose — jnp.matmul accepts
        # it and constant-folds under jit without backend init at __init__
        # Additive pad for subtraction: smallest multiple of p >= RADIX, so
        # (x - y + sub_pad) > 0 for ANY canonical-limb operand (the lazy
        # invariant is tighter, but accepting the full limb capacity makes
        # the API contract unconditional at negligible cost).
        cover_bits = LIMB_BITS * NLIMBS
        c = -(-(1 << cover_bits) // p)  # ceil
        self.sub_pad = int_to_limbs(c * p, -(-(cover_bits + 1) // LIMB_BITS))
        # Lift added before each fold: a multiple of p large enough that
        # the folded value stays non-negative even when relaxed-round
        # borrows leave -1 limbs below FOLD_BASE (lo value >= -2^253) or
        # fold rows act on -1 high limbs (>= -FOLD_ROWS*2^12*p > -2^260).
        self.lift = int_to_limbs(-(-(1 << 261) // p) * p, FOLD_BASE)
        # Shifted moduli for canonicalization: p << k >= RADIX at k_max;
        # descending conditional subtraction brings any canonical-limb
        # value < p.
        k_max = 0
        while (p << k_max) < (1 << cover_bits):
            k_max += 1
        self.pshift = np.stack(
            [int_to_limbs(p << k, NLIMBS + 1) for k in range(k_max, -1, -1)]
        )  # (k_max+1, 26)
        self.zero = np.zeros(NLIMBS, np.int32)
        self.one = int_to_limbs(1)
        self._pad_cache: dict = {}
        self._canon_jit = None  # lazily-jitted canon (see canon())

    # -- normalization ------------------------------------------------------

    def _fold_hi(self, z: jnp.ndarray) -> jnp.ndarray:
        """Fold limbs >= FOLD_BASE back under the modulus; FOLD_BASE wide."""
        hi = z[..., FOLD_BASE:]
        m = hi.shape[-1]
        if m == 0:
            return z
        if m > self.fold_j.shape[0]:  # silent slice-truncation would drop limbs
            raise ValueError(f"accumulator too wide: {m} high limbs > "
                             f"{self.fold_j.shape[0]} fold rows")
        folded = jnp.matmul(hi, self.fold_j[:m])  # (..., 22), <= 33*2^24
        return z[..., :FOLD_BASE] + folded

    def normalize(self, z: jnp.ndarray) -> jnp.ndarray:
        """Reduce any accumulator (..., L) with |limb| < 2^30.7 to lazy
        form: NLIMBS canonical limbs, value in [0, 2^LAZY_BITS), same
        residue mod p — with ONE exact carry.

        Stages: three *relaxed* carry rounds (vectorized, no sequential
        propagation; a dropped top carry is impossible because each round
        extends the width by one limb) bound limbs to [-1, 2^12 + eps];
        one fold brings the width to FOLD_BASE while adding `lift` (a
        multiple of p) so the value stays non-negative despite borrow
        limbs; the single exact carry then canonicalizes into the 3 spare
        top limbs. Value bound: lo < 2^264, fold <= FOLD_ROWS*2^12*p,
        lift < 2^262 — total < 2^273 = 2^LAZY_BITS, so the carry off the
        top limb is provably zero. The exact carry is THE serialized
        lax.scan dominating kernel latency on TPU; one per normalize
        (instead of three for an exact-width form) is the point of the
        25-limb lazy representation.
        """
        pad = [(0, 0)] * (z.ndim - 1)

        def relax3(v):
            for _ in range(3):
                top, v = _relaxed_round(jnp.pad(v, pad + [(0, 1)]))
                # width grew by 1 so the round's own top carry is the new
                # top limb's whole content; `top` here is always 0
            return v

        if LIMB_FORM == "wide":
            z = self._fold_hi(relax3(z)) + self.lift
            return _carry(jnp.pad(z, pad + [(0, NLIMBS - FOLD_BASE)]))

        # "exact" form: the legacy 3-carry ladder producing value < 2^264
        # in exactly 22 canonical limbs.
        z = self._fold_hi(relax3(z))
        z = self._fold_hi(relax3(z))
        z = _carry(jnp.pad(z, pad + [(0, 2)]))
        z = self._fold_hi(z)
        z = _carry(jnp.pad(z, pad + [(0, 1)]))
        z = self._fold_hi(z)
        return _carry(z)

    # -- ring ops (lazy in, lazy out) --------------------------------------

    def add(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        return self.normalize(x + y)

    def sub(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        # x - y + (multiple of p >= 2^LAZY_BITS) keeps the value positive
        # for any lazy x, y; per-limb range [-0xfff, 2*0xfff] is carry-safe.
        w = max(x.shape[-1], self.sub_pad.shape[0])
        diff = jnp.pad(x - y, [(0, 0)] * (x.ndim - 1) + [(0, w - x.shape[-1])])
        return self.normalize(diff + np.pad(self.sub_pad,
                                            (0, w - self.sub_pad.shape[0])))

    def neg(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.sub(jnp.broadcast_to(self.zero, x.shape), x)

    def mul_small(self, x: jnp.ndarray, c: int) -> jnp.ndarray:
        """Multiply by a small non-negative int (c < 2^16)."""
        return self.normalize(x * jnp.int32(c))

    def mul(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """Schoolbook product -> 49 columns -> fold+carry. Batch-first."""
        return self.normalize(self.mul_cols(x, y))

    def mul_cols(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """Raw schoolbook product columns (..., 49), each < 25·2^24.

        Building block for *fused* tower arithmetic (ops/bn256_jax): column
        accumulators of several products can be added/subtracted (with a
        `pad_mult` multiple of p keeping the value non-negative) and reduced
        by a single `normalize`, instead of one normalize per ring op.
        Callers own the int32 range proof: each column must stay < 2^31.
        """
        prod = x[..., :, None] * y[..., None, :]  # (..., 25, 25) 24-bit terms
        return conv_cols(prod)

    def pad_mult(self, bits: int) -> np.ndarray:
        """Limb form of the smallest multiple of p >= 2^bits (cached).

        Added to a column accumulator before subtracting values known to be
        < 2^bits, so the represented value stays non-negative for
        `normalize`. Kept canonical-limbed so it adds < 2^12 per column.
        """
        cached = self._pad_cache.get(bits)
        if cached is None:
            value = -(-(1 << bits) // self.p) * self.p
            nlimbs = -(-value.bit_length() // LIMB_BITS)
            cached = int_to_limbs(value, nlimbs)
            self._pad_cache[bits] = cached
        return cached

    def sqr(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.mul(x, x)

    # -- canonical form & predicates ---------------------------------------

    def canon(self, x: jnp.ndarray) -> jnp.ndarray:
        """Unique representative < p (binary descent conditional subtract).

        Jitted: the descent is ~46 conditional-subtract steps, each with
        an exact carry scan — run EAGERLY (host export paths: to_ints,
        eq on concrete arrays) that is thousands of per-op dispatches
        per call and dominated the e2e suites' wall clock. Under an
        outer jit the wrapper inlines; called eagerly it compiles once
        per shape."""
        if self._canon_jit is None:
            self._canon_jit = jax.jit(self._canon_impl)
        return self._canon_jit(x)

    def _canon_impl(self, x: jnp.ndarray) -> jnp.ndarray:
        z = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 1)])
        for k in range(self.pshift.shape[0]):
            z = _cond_sub(z, self.pshift[k])
        return z[..., :NLIMBS]

    def is_zero(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(self.canon(x) == 0, axis=-1)

    def eq(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(self.canon(x) == self.canon(y), axis=-1)

    def select(self, cond: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """Branchless select: cond (...,) bool -> limbs from x else y."""
        return jnp.where(cond[..., None], x, y)

    # -- exponentiation -----------------------------------------------------

    def pow_static(self, x: jnp.ndarray, e: int) -> jnp.ndarray:
        """x^e for a *compile-time* exponent, as a lax.scan over its bits
        (right-to-left square-and-multiply; branchless select per bit)."""
        if e == 0:
            return jnp.broadcast_to(self.one, x.shape)
        bits = jnp.asarray(
            np.array([(e >> i) & 1 for i in range(e.bit_length())], np.int32)
        )

        def step(carry, bit):
            acc, base = carry
            acc = self.select(bit == 1, self.mul(acc, base), acc)
            return (acc, self.sqr(base)), None

        # + x*0: init inherits x's varying manual axes under shard_map
        acc0 = jnp.broadcast_to(self.one, x.shape) + x * 0
        (acc, _), _ = lax.scan(step, (acc0, x), bits)
        return acc

    def inv(self, x: jnp.ndarray) -> jnp.ndarray:
        """Modular inverse by Fermat (p prime). inv(0) = 0."""
        return self.pow_static(x, self.p - 2)

    # -- host conversions ---------------------------------------------------

    def to_ints(self, x) -> np.ndarray:
        return _limbs_to_int_nd(np.asarray(self.canon(x)))

    def from_int(self, v: int) -> jnp.ndarray:
        return jnp.asarray(int_to_limbs(v % self.p))

    def from_ints(self, values: Sequence[int]) -> jnp.ndarray:
        return jnp.asarray(ints_to_limbs([v % self.p for v in values]))


def _cond_sub(z: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """If z >= w (limb arrays, canonical limbs), z - w, else z. Branchless."""
    borrow, out = _carry_scan(z - w)
    ge = borrow == 0  # no net borrow -> z >= w
    return jnp.where(ge[..., None], out, z)
