"""Packed point rows (ISSUE 36): a committee row as ONE byte string, from
the wire codec to the limb planes.

- `crypto.pointrows.PackedRow` stands for the list of points it packs:
  length, truth, indexing and iteration are the list's, the tuples are
  `codec.dec_g1` / `dec_g2`'s.
- The codec's two wire forms of a row (a 0x string, a list of points)
  round-trip, are told apart by the row's JSON type, and may be mixed.
- The limb marshal's packed entry gives the integer entry's planes bit
  for bit, `% P` included; the reference is the per-slot loop the
  converters had before, kept here.
"""

import copy
import json
import pickle
import random

import numpy as np
import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.crypto.pointrows import (G1_POINT_BYTES,
                                               G2_POINT_BYTES, PackedRow,
                                               pack_row)
from gethsharding_tpu.rpc import codec

P = bls.P
GROUPS = {
    "g1": (G1_POINT_BYTES, codec.enc_g1_rows, codec.dec_g1_rows,
           codec.enc_g1, codec.dec_g1),
    "g2": (G2_POINT_BYTES, codec.enc_g2_rows, codec.dec_g2_rows,
           codec.enc_g2, codec.dec_g2),
}


def _point(rng, group, below=P):
    """Any coordinates will do: neither the codec nor the marshal asks
    whether a point lies on its curve."""
    if group == "g1":
        return (rng.randrange(below), rng.randrange(below))
    return (bls.Fp2(rng.randrange(below), rng.randrange(below)),
            bls.Fp2(rng.randrange(below), rng.randrange(below)))


def _rows(group, lengths, seed=36):
    rng = random.Random(seed)
    return [[_point(rng, group) for _ in range(n)] for n in lengths]


# == the row type ===========================================================


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_a_packed_row_stands_for_its_list_of_points(group):
    size, _, _, enc_point, dec_point = GROUPS[group]
    points = _rows(group, [5])[0]
    row = pack_row(points, size)
    assert isinstance(row, PackedRow) and len(row.raw) == 5 * size
    assert len(row) == 5 and bool(row)
    assert list(row) == points == [row[i] for i in range(5)]
    assert row[-1] == points[-1] and row[1:3] == points[1:3]
    # exactly the tuples the listed wire form decodes to
    wire = json.loads(json.dumps([enc_point(p) for p in points]))
    assert [dec_point(v) for v in wire] == list(row)
    assert all(type(a) is type(b) for a, b in zip(row, points))
    assert row == points and row == pack_row(points, size)
    assert row != points[:4] and pack_row(row, size) is row
    with pytest.raises(IndexError):
        row[5]
    with pytest.raises(AttributeError):
        row.raw = b""
    for twin in (pickle.loads(pickle.dumps(row)), copy.deepcopy(row)):
        assert twin == row and twin.point_size == size
    empty = pack_row([], size)
    assert len(empty) == 0 and not empty and list(empty) == []


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("fault", ["none_slot", "too_wide", "negative"])
def test_what_the_packed_form_cannot_hold_stays_a_list(group, fault):
    size = GROUPS[group][0]
    points = _rows(group, [3])[0]
    if fault == "none_slot":
        points[1] = None
    else:
        bad = 1 << 256 if fault == "too_wide" else -1
        points[1] = ((bad, 1) if group == "g1"
                     else (bls.Fp2(1, bad), bls.Fp2(2, 3)))
    assert pack_row(points, size) is None


def test_a_packed_row_is_a_whole_number_of_points():
    with pytest.raises(ValueError):
        PackedRow(b"\x00" * 65, G1_POINT_BYTES)
    with pytest.raises(ValueError):
        PackedRow(b"\x00" * 64, 48)
    # a G1 row is no G2 row, whatever its length
    assert pack_row(PackedRow(b"\x00" * 128, G1_POINT_BYTES),
                    G2_POINT_BYTES) is None


# == the wire codec =========================================================


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("case", ["ragged", "empty_row", "none_listed",
                                  "mixed", "relayed"])
def test_row_codec_round_trip(group, case):
    size, enc_rows, dec_rows, enc_point, _ = GROUPS[group]
    rows = _rows(group, [3, 1, 4])
    if case == "empty_row":
        rows[1] = []
    elif case == "none_listed":
        rows[1], rows[2][2] = [None], None
    elif case == "mixed":               # one listed row between packed ones
        rows[1] = [None]
    elif case == "relayed":
        rows = [pack_row(r, size) for r in rows]
    wire = json.loads(json.dumps(enc_rows(rows)))
    listed = [i for i, r in enumerate(rows) if None in list(r)]
    for i, row in enumerate(wire):
        if i in listed:
            # today's nested lists, null where the point is absent
            assert row == [enc_point(p) for p in rows[i]]
        else:
            assert isinstance(row, str) and row.startswith("0x")
            assert len(row) == 2 + 2 * size * len(rows[i])
    if case == "relayed":
        assert wire == ["0x" + r.raw.hex() for r in rows]
    got = dec_rows(wire)
    assert [isinstance(r, PackedRow) for r in got] \
        == [i not in listed for i in range(3)]
    assert [list(r) for r in got] == [list(r) for r in rows]
    # the old form of the same rows still decodes, to the same points
    old = json.loads(json.dumps([[enc_point(p) for p in r] for r in rows]))
    assert [list(r) for r in dec_rows(old)] == [list(r) for r in rows]


def test_the_byte_and_coordinate_order_of_a_packed_row():
    g1 = codec.enc_g1_rows([[(1, 2)]])[0]
    assert g1 == "0x" + "00" * 31 + "01" + "00" * 31 + "02"
    g2 = codec.enc_g2_rows([[(bls.Fp2(1, 2), bls.Fp2(3, 4))]])[0]
    assert g2 == "0x" + "".join("00" * 31 + "0%d" % c for c in (1, 2, 3, 4))
    assert codec.enc_g1_rows([[]]) == ["0x"]


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("row", ["0x" + "00" * 65, "0x0", "0xzz", "00" * 63])
def test_a_malformed_packed_row_is_a_value_error(group, row):
    with pytest.raises(ValueError):
        GROUPS[group][2]([row])


def test_the_committee_call_counts_rows_packed_in_both_halves():
    sigs, pks = _rows("g1", [2, 2, 0, 2]), _rows("g2", [2, 2, 0, 2])
    sigs[1][0] = None                   # listed signatures, packed keys
    wire = json.loads(json.dumps([
        [codec.enc_bytes(b"m%d" % i) for i in range(4)],
        codec.enc_g1_rows(sigs), codec.enc_g2_rows(pks),
        codec.enc_pk_row_keys([("k", 0), None, None, ("k", 3)])]))
    wire[2][3] = [codec.enc_g2(p) for p in pks[3]]   # an old client's row
    msgs, got_sigs, got_pks, keys, packed = codec.dec_committee_call(*wire)
    assert msgs == [b"m0", b"m1", b"m2", b"m3"]
    assert keys == ["('k', 0)", None, None, "('k', 3)"]
    assert packed == 2                  # rows 0 and 2 (the empty row)
    assert [list(r) for r in got_sigs] == sigs
    assert [list(r) for r in got_pks] == pks
    assert codec.dec_committee_call([], [], [], None) == ([], [], [], None, 0)


# == the limb marshal =======================================================


def _reference_planes(rows, width, out_dtype, group):
    """The converters' per-slot loop as it stood before the packed entry."""
    from gethsharding_tpu.ops.limb import NLIMBS, ints_to_limbs

    flat_x, flat_y = [], []
    mask = np.zeros((len(rows), width), bool)
    for b, row in enumerate(rows):
        for c in range(width):
            pt = row[c] if c < len(row) else None
            if pt is None:
                flat_x.extend((0,) * (1 if group == "g1" else 2))
                flat_y.extend((0,) * (1 if group == "g1" else 2))
                continue
            if group == "g1":
                flat_x.append(pt[0] % P)
                flat_y.append(pt[1] % P)
            else:
                x, y = pt
                flat_x.extend((x.a % P, x.b % P))
                flat_y.extend((y.a % P, y.b % P))
            mask[b, c] = True
    both = ints_to_limbs(flat_x + flat_y, out_dtype=out_dtype)
    shape = (len(rows), width) + ((NLIMBS,) if group == "g1"
                                  else (2, NLIMBS))
    half = len(both) // 2
    return both[:half].reshape(shape), both[half:].reshape(shape), mask


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("over", [False, True], ids=["below_p", "not_below_p"])
@pytest.mark.parametrize("width", [16, 144])
@pytest.mark.parametrize("out_dtype", [np.int32, np.uint16],
                         ids=["int32", "uint16"])
def test_packed_planes_equal_the_integer_entrys_bit_for_bit(
        group, over, width, out_dtype):
    from gethsharding_tpu.ops import bn256_jax

    size = GROUPS[group][0]
    convert = getattr(bn256_jax, f"{group}_committee_to_limbs")
    rng = random.Random(width + over)
    lengths = [width, 1, 0, width - 3, 2, width // 2]
    listed = _rows(group, lengths, seed=width)
    # coordinates at the edges of the top-byte screen, all below P
    listed[1][0] = ((P - 1, 0) if group == "g1"
                    else (bls.Fp2(P - 1, 0), bls.Fp2(P >> 8 << 8, 1)))
    over_rows = []
    if over:
        # P itself, the largest 32-byte value, and one step above P's
        # top byte: hostile coordinates the verifier reduces mod P
        over_rows = [0, 4]
        listed[0][width - 1] = _point(rng, group, below=1 << 256)
        listed[0][0] = ((P, 1) if group == "g1"
                        else (bls.Fp2(3, P), bls.Fp2(4, 5)))
        listed[4][1] = (((1 << 256) - 1, P + 1) if group == "g1" else
                        (bls.Fp2(1, 2), bls.Fp2((1 << 256) - 1, P + 1)))
    packed = [pack_row(r, size) for r in listed]
    assert all(isinstance(r, PackedRow) for r in packed)
    want = _reference_planes(listed, width, out_dtype, group)
    counter = metrics.counter("sig/marshal/int_rows")
    for rows, int_rows in ((listed, 0), (packed, len(over_rows)),
                           # beside packed rows a listed row is counted
                           # (row 2 is empty; row 0 is packed and over)
                           (packed[:3] + listed[3:], 3 + over)):
        before = counter.value
        got = convert(rows, width, out_dtype=out_dtype)
        assert counter.value - before == int_rows
        for a, b in zip(want, got):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b)
            assert b.flags.c_contiguous
    # the mask of a packed row has no hole; a listed None has one
    holed = [list(r) for r in listed]
    holed[0][2] = None
    want = _reference_planes(holed, width, out_dtype, group)
    got = convert(packed[1:2] + holed[:1] + packed[2:], width,
                  out_dtype=out_dtype)
    order = [1, 0, 2, 3, 4, 5]
    for a, b in zip(want, got):
        assert np.array_equal(a[order], b)
    with pytest.raises(ValueError, match="exceeds width"):
        convert([pack_row(_rows(group, [width + 1])[0], size)], width)


@pytest.mark.parametrize("block_rows", [None, 5, 24])
def test_ints_to_limbs_is_its_two_steps(monkeypatch, block_rows):
    from gethsharding_tpu.ops import limb

    if block_rows:      # several blocks, the last one ragged (or exact)
        monkeypatch.setattr(limb, "_BLOCK_ROWS", block_rows)
    rng = random.Random(7)
    values = [0, 1, P - 1, (1 << 256) - 1] + [rng.randrange(P)
                                              for _ in range(20)]
    for nlimbs in (limb.NLIMBS, 22, 3):
        vals = [v % (1 << (limb.LIMB_BITS * nlimbs)) for v in values]
        raw = limb.ints_to_bytes(vals, nlimbs)
        assert raw.dtype == np.uint8 and raw.shape == (
            len(vals), -(-nlimbs * limb.LIMB_BITS // 8))
        assert [int.from_bytes(r.tobytes(), "little") for r in raw] == vals
        got = limb.bytes_to_limbs(raw, nlimbs)
        assert np.array_equal(got, limb.ints_to_limbs(vals, nlimbs))
        assert np.array_equal(got, np.stack(
            [limb.int_to_limbs(v, nlimbs) for v in vals]))
    assert limb.ints_to_limbs([]).shape == (0, limb.NLIMBS)
    with pytest.raises(ValueError):
        limb.ints_to_limbs([-1])
    with pytest.raises(ValueError):
        limb.ints_to_limbs([1 << 36], nlimbs=3)
    with pytest.raises(ValueError):     # 25 limbs: 4 spare bits of 38 bytes
        limb.bytes_to_limbs(np.full((1, 38), 0xFF, np.uint8), 25)
