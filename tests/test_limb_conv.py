"""The limb product's column sum at module defaults (PR 29), in the fast
tier: `tests/test_limb.py` is auto-marked `slow` for its inversion
chains, and these cases compile nothing heavy.

`limb.conv_cols` at defaults is the padded-row sum that took the
re-viewing form's place under the name `shift`; every field product of
every kernel goes through it (`ModArith.mul_cols`,
`bn256_jax._pair_conv_combine`). `test_limb.py::test_conv_impls_agree`
holds the other forms to the same columns.
"""

import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gethsharding_tpu.crypto import bn256 as bn_ref
from gethsharding_tpu.ops import limb

# the product shapes the benchmark's cells trace: the recompute kernel's
# fp12 square at 112 rows, a table-fed line multiply on a 56-row lane
# block, one field product of the one-row vote, the aggregation tree
_CONV_CELLS = [(112, 6, 2, 2, 25, 25), (56, 3, 2, 2, 25, 25), (1, 25, 25),
               (144, 25, 25)]




def _shape_id(shape):
    return "x".join(map(str, shape))


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
def test_conv_default_form_on_cell_shapes(shape, fill):
    """`conv_cols` at module defaults (the padded-row sum that took the
    re-viewing form's place as `shift`) returns the one-hot
    contraction's columns bit for bit, on the product shapes the
    benchmark's cells trace, with random limbs and with every limb at
    the range's edge (4095: columns reach 25 * 4095^2 < 2^29)."""
    x, y = _limbs_for(shape, fill, seed=sum(shape))
    prod = jnp.asarray(x)[..., :, None] * jnp.asarray(y)[..., None, :]
    assert prod.shape == shape
    want = np.asarray(limb.conv_cols(prod, impl="onehot"))
    got = np.asarray(limb.conv_cols(prod))
    assert got.dtype == np.int32 and np.array_equal(got, want)
    if fill == "edge":
        n = np.arange(shape[-2] + shape[-1] - 1)
        terms = np.minimum(n, n[::-1]) + 1
        assert np.array_equal(got.reshape(-1, n.size)[0],
                              terms * limb.LIMB_MASK ** 2)


def test_mul_lowers_without_product_sized_reshape():
    """The lowered text of `ModArith.mul` at defaults re-views nothing
    product-sized: no `reshape` whose operand or result holds the
    product's 625 words a row (or the 1,250 of its padded form). On
    the chip such a reshape changes the minor dimension of a tiled
    array, a physical re-laying of every word: 507 ms of the keyed
    period audit before PR 29 (PERF.md section 6)."""
    if limb.CONV_IMPL != "shift":
        pytest.skip("the property is the default form's")
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
