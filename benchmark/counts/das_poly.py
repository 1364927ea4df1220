"""Operation and byte counts of the multiproof dispatch's fixed-base
MSMs (`ops/bn256_jax.das_poly_verify_batch`), for a roofline share.

    python benchmark/counts/das_poly.py [bucket] [terms] [window]

A row sums `terms` G1 scalars (the interpolation coefficients) and
`terms + 1` G2 scalars (the vanishing coefficients), each as 256/w
gathered table entries, with the complete projective adder of RCB16
algorithm 7 (`_proj_add_impl`: 12 field products, 2 products by 3b,
14 additions, 5 subtractions), then one G1 add for A = C - R.

The 32-bit operations counted are the limb products' multiplies and
the column sums that accumulate them, 2 * L^2 for an Fp product of
L-limb operands and four Fp products for an Fp2 product: a floor, as
the normalizations, additions and the gather are left out, so a share
computed from it is a floor too. The HBM bytes are the gathered points
read once, and each tree level's sums written and read once: also a
floor of what moves. Nothing here is measured; it is arithmetic from
the shapes.
"""

from __future__ import annotations

import json
import sys

NLIMBS = 25        # ops/limb.py at its default "wide" form
COORDS = 3         # projective X, Y, Z


def point_adds(bucket: int, terms: int, window: int = 4) -> dict:
    """Point additions per dispatch: the tree sums of the gathered
    terms (n terms take n - 1 adds) and the fold A = C - R."""
    windows = 256 // window
    g1 = bucket * (terms * windows - 1 + 1)
    g2 = bucket * ((terms + 1) * windows - 1)
    return {"g1": g1, "g2": g2}


def int32_ops(bucket: int, terms: int, window: int = 4,
              nlimbs: int = NLIMBS) -> int:
    """The floor of 32-bit operations of both MSMs and the fold."""
    fp_mul = 2 * nlimbs * nlimbs
    g1_add = 12 * fp_mul + 2 * nlimbs          # 2 products by 9: limb x small
    g2_add = 14 * 4 * fp_mul                   # 12 products + 2 by b3, in Fp2
    adds = point_adds(bucket, terms, window)
    return adds["g1"] * g1_add + adds["g2"] * g2_add


def hbm_bytes(bucket: int, terms: int, window: int = 4,
              nlimbs: int = NLIMBS) -> int:
    """The floor of HBM bytes: the gathered entries read, every tree
    level's sums written once and read once (about twice the entries
    again), the digit planes read."""
    windows = 256 // window
    g1_point = COORDS * nlimbs * 4
    g2_point = COORDS * 2 * nlimbs * 4
    gathered = bucket * windows * (terms * g1_point + (terms + 1) * g2_point)
    digits = bucket * (2 * terms + 1) * windows
    return gathered + 2 * gathered + digits


def table_bytes(powers_g1: int = 64, powers_g2: int = 65, window: int = 4,
                nlimbs: int = NLIMBS) -> int:
    """The resident SRS tables: (powers x 256/w windows x 2^w entries)
    projective points of each group."""
    entries = (256 // window) << window
    return entries * COORDS * nlimbs * 4 * (powers_g1 + 2 * powers_g2)


def counts(bucket: int = 112, terms: int = 16, window: int = 4) -> dict:
    return {"bucket": bucket, "terms": terms, "window": window,
            "point_adds": point_adds(bucket, terms, window),
            "int32_ops": int32_ops(bucket, terms, window),
            "hbm_bytes": hbm_bytes(bucket, terms, window),
            "table_bytes": table_bytes(window=window)}


if __name__ == "__main__":
    print(json.dumps(counts(*(int(a) for a in sys.argv[1:4]))))
