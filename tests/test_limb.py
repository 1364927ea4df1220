"""Differential tests: batched limb arithmetic vs. Python big ints.

Covers both consensus moduli (bn256 base/scalar fields, secp256k1 base/
scalar fields) — the same ModArith machinery backs the pairing kernel and
the ECDSA kernel, mirroring how the reference's gfP asm and libsecp256k1
field code each serve one curve (SURVEY.md §2.3).

The limb product's column sum (`limb.conv_cols`, which every field
product of every kernel goes through: `ModArith.mul_cols`,
`bn256_jax._pair_conv_combine`) is held to a plain NumPy schoolbook.
"""

import ast
import math
import os
import random
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
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


def _schoolbook_cols(prod: np.ndarray) -> np.ndarray:
    """out[n] = sum over l of prod[l, n-l], in plain NumPy (int64)."""
    L, M = prod.shape[-2:]
    out = np.zeros(prod.shape[:-2] + (L + M - 1,), np.int64)
    for l in range(L):
        out[..., l:l + M] += prod[..., l, :]
    return out


def _shape_id(shape):
    return "x".join(map(str, shape))


@pytest.mark.parametrize("shape", [(22, 22), (25, 49), (3, 7), (1, 5),
                                   (22, 43)], ids=_shape_id)
def test_conv_cols_matches_schoolbook(shape):
    """Anti-diagonal sums of signed entries, square and ragged shapes."""
    L, M = shape
    rng = np.random.default_rng(7 + 100 * L + M)
    prod = rng.integers(-2**20, 2**20, size=(2, 3, L, M),
                        dtype=np.int64).astype(np.int32)
    got = np.asarray(limb.conv_cols(jnp.asarray(prod)))
    assert got.dtype == np.int32
    assert np.array_equal(got, _schoolbook_cols(prod))


# the product shapes the benchmark's cells trace: the recompute kernel's
# fp12 square at 112 rows, a table-fed line multiply on a 56-row lane
# block, one field product of the one-row vote, the aggregation tree
_CONV_CELLS = [(112, 6, 2, 2, 25, 25), (56, 3, 2, 2, 25, 25), (1, 25, 25),
               (144, 25, 25)]


def _limbs_for(shape, fill, seed):
    """The two operands of a product of shape (..., L, M): random
    canonical limbs, or every limb at the range's edge."""
    x_shape, y_shape = shape[:-1], shape[:-2] + shape[-1:]
    if fill == "edge":
        return (np.full(x_shape, limb.LIMB_MASK, np.int32),
                np.full(y_shape, limb.LIMB_MASK, np.int32))
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 1 << limb.LIMB_BITS, x_shape).astype(np.int32),
            rng.integers(0, 1 << limb.LIMB_BITS, y_shape).astype(np.int32))


@pytest.mark.parametrize("fill", ["random", "edge"])
@pytest.mark.parametrize("shape", _CONV_CELLS, ids=_shape_id)
def test_conv_cols_on_cell_shapes(shape, fill):
    """`conv_cols` returns the schoolbook's columns bit for bit on the
    product shapes the benchmark's cells trace, with random limbs and
    with every limb at the range's edge (4095: columns reach
    25 * 4095^2 < 2^29)."""
    x, y = _limbs_for(shape, fill, seed=sum(shape))
    prod = jnp.asarray(x)[..., :, None] * jnp.asarray(y)[..., None, :]
    assert prod.shape == shape
    got = np.asarray(limb.conv_cols(prod))
    assert got.dtype == np.int32
    assert np.array_equal(got, _schoolbook_cols(np.asarray(prod)))
    if fill == "edge":
        n = np.arange(shape[-2] + shape[-1] - 1)
        terms = np.minimum(n, n[::-1]) + 1
        assert np.array_equal(got.reshape(-1, n.size)[0],
                              terms * limb.LIMB_MASK ** 2)


def test_mul_lowers_without_product_sized_reshape():
    """The lowered text of `ModArith.mul` re-views nothing
    product-sized: no `reshape` whose operand or result holds the
    product's 625 words a row (or the 1,250 of its padded form). On
    the chip such a reshape changes the minor dimension of a tiled
    array, a physical re-laying of every word: 507 ms of the keyed
    period audit before PR 29 (PERF.md section 6)."""
    rows = 8
    fp = limb.ModArith(bn_ref.P)
    arg = jax.ShapeDtypeStruct((rows, limb.NLIMBS), jnp.int32)
    text = jax.jit(fp.mul).lower(arg, arg).as_text()
    assert "stablehlo.multiply" in text
    product_words = rows * limb.NLIMBS * limb.NLIMBS
    reshapes = re.findall(
        r"stablehlo\.reshape[^\n]*?tensor<([0-9x]+)xi32>\) -> "
        r"tensor<([0-9x]+)xi32>", text)
    words = [math.prod(map(int, dims.split("x")))
             for pair in reshapes for dims in pair]
    assert max(words, default=0) < product_words, reshapes


# == one kernel path ========================================================

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the import-time switches left: the 22-limb form and the Pallas
# aggregation (PERF.md section 6); the platform chooses the pairing's
# kernels (`bn256_jax.pairing_in_pallas`)
_SWITCHES_LEFT = {
    "gethsharding_tpu/ops/limb.py": {"GETHSHARDING_TPU_LIMB_FORM"},
    "gethsharding_tpu/ops/bn256_jax.py": {"GETHSHARDING_TPU_AGG"},
}
# the nine that went, each with the code only it reached, or the
# choice the platform makes now
_SWITCHES_GONE = ("CARRY", "PALLAS", "NORM", "CONV", "PAIRCONV",
                  "PAIR_UNROLL", "SCAN_UNROLL", "FINALEXP", "MILLER")


def _env_reads(tree: ast.AST) -> list:
    """The variable each touch of `os.environ` / `os.getenv` reads:
    its name for `os.environ.get("X", ...)` and `os.getenv("X")`, None
    for any other use."""
    named = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        touch = node.func.value if node.func.attr == "get" else node.func
        if (isinstance(touch, ast.Attribute) and node.args
                and isinstance(node.args[0], ast.Constant)):
            named[id(touch)] = node.args[0].value
    return [named.get(id(node)) for node in ast.walk(tree)
            if isinstance(node, ast.Attribute)
            and node.attr in ("environ", "environb", "getenv")]


def test_kernel_modules_read_no_other_switch():
    """`ops/limb.py` and `ops/bn256_jax.py` read the two variables left
    and nothing else, and a value the nine removed switches would have
    refused at import changes nothing."""
    for rel, allowed in _SWITCHES_LEFT.items():
        with open(os.path.join(_REPO, rel)) as src:
            reads = _env_reads(ast.parse(src.read()))
        assert set(reads) == allowed, (rel, reads)
    env = {key: val for key, val in os.environ.items()
           if not key.startswith("GETHSHARDING_TPU_")}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO})
    env.update({f"GETHSHARDING_TPU_{name}": "nonsense"
                for name in _SWITCHES_GONE})
    out = subprocess.run(
        [sys.executable, "-c",
         "from gethsharding_tpu.ops import bn256_jax, limb\n"
         "print(limb.NLIMBS, bn256_jax.NLIMBS)"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["25", "25"]
