"""A row of bn256 points as ONE byte string.

The committee plane moves rows of 90..135 points: a period request holds
11,138 G1 signatures and as many G2 keys. As Python objects that is
22,000 tuples over 67,000 big integers, which the wire codec turns into
hex strings and the limb marshal turns back into bytes. A `PackedRow`
keeps the row in the form both ends want: the points' coordinates, each
32 bytes big-endian, back to back.

- G1, 64 bytes a point: ``x ‖ y`` (the reference's `bn256.G1.Marshal`).
- G2, 128 bytes a point: ``x.a ‖ x.b ‖ y.a ‖ y.b`` with ``Fp2 = a + b·i``
  (real part first: the order of the JSON form ``[[xa, xb], [ya, yb]]``
  and of `bn256._pk_bytes`; NOT cloudflare's `G2.Marshal`, which puts
  the imaginary part first).

The packed form has no absent point: a row with a `None` slot stays a
list. Sits beside `bn256` so the wire codec (`rpc/codec.py`) and the
limb marshal (`ops/bn256_jax.py`) can both import it.
"""

from __future__ import annotations

from collections.abc import Sequence
from typing import Optional

from gethsharding_tpu.crypto.bn256 import Fp2

COORD_BYTES = 32
G1_POINT_BYTES = 2 * COORD_BYTES
G2_POINT_BYTES = 4 * COORD_BYTES


class PackedRow(Sequence):
    """An immutable row of points held as bytes. `len`, truth, indexing
    and iteration are those of the list of points it stands for, and
    yield exactly the tuples `codec.dec_g1` / `dec_g2` yield, so a
    consumer that walks points works unchanged and pays only if it
    walks."""

    __slots__ = ("raw", "point_size")

    def __init__(self, raw: bytes, point_size: int):
        if point_size not in (G1_POINT_BYTES, G2_POINT_BYTES):
            raise ValueError(f"point size {point_size}: want "
                             f"{G1_POINT_BYTES} (G1) or {G2_POINT_BYTES} (G2)")
        if len(raw) % point_size:
            raise ValueError(f"{len(raw)} bytes are no whole number of "
                             f"{point_size}-byte points")
        object.__setattr__(self, "raw", bytes(raw))
        object.__setattr__(self, "point_size", point_size)

    def __setattr__(self, name, value):
        raise AttributeError("PackedRow is immutable")

    def __reduce__(self):
        return (PackedRow, (self.raw, self.point_size))

    def __len__(self) -> int:
        return len(self.raw) // self.point_size

    def _points(self, raw: bytes):
        """An iterator over the points `raw` packs."""
        c = [int.from_bytes(raw[o:o + COORD_BYTES], "big")
             for o in range(0, len(raw), COORD_BYTES)]
        if self.point_size == G1_POINT_BYTES:
            return zip(c[0::2], c[1::2])
        return ((Fp2(xa, xb), Fp2(ya, yb)) for xa, xb, ya, yb
                in zip(c[0::4], c[1::4], c[2::4], c[3::4]))

    def __iter__(self):
        return self._points(self.raw)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        n = len(self)
        if not -n <= i < n:
            raise IndexError("point index out of range")
        at = (i % n) * self.point_size
        return next(self._points(self.raw[at:at + self.point_size]))

    def __eq__(self, other):
        if isinstance(other, PackedRow):
            return (self.point_size == other.point_size
                    and self.raw == other.raw)
        if isinstance(other, (list, tuple)):
            return len(self) == len(other) and list(self) == list(other)
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        kind = "G1" if self.point_size == G1_POINT_BYTES else "G2"
        return f"PackedRow({kind}, {len(self)} points)"


def pack_row(row, point_size: int) -> Optional[PackedRow]:
    """A row of points packed, or None where the packed form cannot hold
    it: a `None` slot, a coordinate outside [0, 2^256) or no Python
    integer. A row that is packed already is returned as it is."""
    if isinstance(row, PackedRow):
        return row if row.point_size == point_size else None
    try:
        if point_size == G1_POINT_BYTES:
            raw = b"".join([c.to_bytes(COORD_BYTES, "big")
                            for x, y in row for c in (x, y)])
        else:
            raw = b"".join([c.to_bytes(COORD_BYTES, "big")
                            for x, y in row for c in (x.a, x.b, y.a, y.b)])
    except (TypeError, OverflowError, AttributeError):
        # TypeError: the unpack of a None slot
        return None
    return PackedRow(raw, point_size)
