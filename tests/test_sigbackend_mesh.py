"""Mesh dispatch: tri-layout bit-identity + per-device cache shards.

The acceptance contracts of the `sigbackend/` package split's mesh
path, exercised on the conftest-forced 8-device virtual CPU mesh:

- `bls_verify_committees{,_async}` and `das_verify_multiproofs` return
  BIT-IDENTICAL verdicts across the 1-, 2- and 8-device layouts and
  the scalar reference — including empty committees, infinity-point
  slots, forged rows, malformed multiproof rows and the degenerate
  infinity-proof row;
- the mesh committee step is non-vacuous: `last_mesh` shows the
  verdict plane really sharded over every device, exactly ONE
  cross-device collective (the vote-total psum) per compiled step, and
  a psum'd vote total agreeing with the verdict plane;
- the per-device cache shards churn correctly under a starvation
  byte budget (evictions tick, verdicts stay bit-identical, shards end
  empty — churn, not growth) and own pairwise-DISJOINT buffer sets
  under their per-shard devscope census owners.

The host-only geometry/marshal tests at the top stay in the fast tier;
everything that compiles a pairing kernel is marked `slow`
(run_suite.sh runs this file in its own process like the other kernel
suites).
"""

import functools
import random

import pytest

from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.rpc import codec
from gethsharding_tpu.sigbackend import PythonSigBackend, get_backend
from gethsharding_tpu.sigbackend import marshal
from gethsharding_tpu.sigbackend.layout import (DeviceLayout,
                                                count_collectives)

# -- marshal: padding policy and the u16 wire (host-only, fast tier) -------


def test_bucket_size_quarter_pow2_policy():
    assert [marshal.bucket_size(n) for n in (0, 1, 2, 3, 5, 8)] == \
        [1, 1, 2, 4, 8, 8]
    assert marshal.bucket_size(9) == 10    # quarter steps above 8
    assert marshal.bucket_size(65) == 80   # the docstring's worst case
    assert marshal.bucket_size(100) == 112  # the 100-shard audit shape
    # idempotent: a bucket is its own bucket (serving sizes flush
    # quanta with the same function)
    for n in (1, 2, 4, 8, 10, 80, 112):
        assert marshal.bucket_size(n) == n


def test_committee_width_policy():
    assert marshal.committee_width([[1, 2, 3]], [[1, 2]]) == 4
    assert marshal.committee_width([[]], [[]]) == 1  # empty -> min width
    # above 32: next multiple of 16, driven by the WIDEST row anywhere
    assert marshal.committee_width([[0] * 135], [[0] * 7]) == 144


def test_wire_dtype_and_narrowing():
    import numpy as np

    assert marshal.wire_dtype(False, False) is np.int32
    assert marshal.wire_dtype(True, False) is np.uint16
    # GETHSHARDING_CHECK keeps planes wide so the narrowing site checks
    assert marshal.wire_dtype(True, True) is np.int32
    canonical = np.array([[0, 7, marshal.U16_LIMB_BOUND - 1]], np.int32)
    out = marshal.narrow_u16(canonical, check=True)
    assert out.dtype == np.uint16 and (out == canonical).all()
    # a wide-form limb survives the cast but violates kernel headroom:
    # only the checked mode may see it
    wide = np.array([marshal.U16_LIMB_BOUND], np.int32)
    with pytest.raises(AssertionError):
        marshal.narrow_u16(wide, check=True)
    with pytest.raises(AssertionError):
        marshal.assert_canonical_limbs(canonical, wide)
    conv = marshal.wire_converter(True, False)
    assert conv(canonical).dtype == np.uint16
    assert marshal.wire_converter(False, False)(canonical).dtype == np.int32


def test_normalize_row_keys():
    assert marshal.normalize_row_keys(None, 4) is None
    # short caller list -> trailing rows uncached; surplus dropped
    assert marshal.normalize_row_keys(["a", "b"], 4) == \
        ["a", "b", None, None]
    assert marshal.normalize_row_keys(["a", "b", "c"], 2) == ["a", "b"]


# -- layout: geometry and the collective ledger (fast tier) ----------------


def test_count_collectives_on_hlo_text():
    hlo = """\
ENTRY main {
  %p0 = f32[8]{0} parameter(0)
  %ar = f32[8]{0} all-reduce(%p0), replica_groups={}
  %ag = f32[16]{0} all-gather-start(%p0), dimensions={0}
  %agd = f32[16]{0} all-gather-done(%ag)
  %sum = f32[8]{0} add(%p0, %p0)
}
"""
    # async pairs count ONCE (on the start half); local ops never
    assert count_collectives(hlo) == 2
    assert count_collectives("add(%a, %b)") == 0


def test_single_device_layout_is_the_default():
    lay = DeviceLayout(1)
    assert not lay.is_mesh and lay.mesh is None
    # no mesh -> the bucket policy is untouched
    for n in (1, 5, 9, 100):
        assert lay.mesh_bucket(n) == marshal.bucket_size(n)


def test_mesh_layout_geometry():
    import jax
    import numpy as np

    if jax.device_count() < 4:
        pytest.skip("needs the virtual multi-device mesh (conftest)")
    lay = DeviceLayout(4)
    assert lay.is_mesh and len(lay.devices) == 4
    # buckets round UP to a device multiple so the split is even
    assert lay.mesh_bucket(9) == 12  # bucket_size(9)=10 -> 12
    assert lay.mesh_bucket(8) == 8
    assert lay.rows_per_device(12) == 3
    assert [lay.device_of_row(r, 12) for r in (0, 2, 3, 11)] == \
        [0, 0, 1, 3]
    # place: one host plane -> contiguous per-device slabs
    host = np.arange(24, dtype=np.int32).reshape(12, 2)
    placed = lay.place(host)
    assert len(placed.sharding.device_set) == 4
    assert (np.asarray(placed) == host).all()
    # assemble: per-device slabs already resident -> one global array,
    # zero bytes moved
    slabs = [jax.device_put(host[i * 3:(i + 1) * 3], dev)
             for i, dev in enumerate(lay.devices)]
    whole = lay.assemble(slabs)
    assert whole.shape == (12, 2)
    assert (np.asarray(whole) == host).all()


def test_the_mesh_step_gets_the_same_planes_from_packed_rows():
    """The mesh twin's marshal (fast tier: the step itself is stubbed,
    its compile is the slow tier's): rows that arrive packed off the
    wire give the pjit'd step the planes the listed rows give it, bit
    for bit, split over both devices; the `None` row stays listed."""
    import jax
    import numpy as np

    from gethsharding_tpu.crypto.pointrows import PackedRow
    from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend

    if jax.device_count() < 2:
        pytest.skip("needs the virtual multi-device mesh (conftest)")
    messages, sig_rows, pk_rows, _ = _committee_cols()
    packed_sigs = codec.dec_g1_rows(codec.enc_g1_rows(sig_rows))
    packed_pks = codec.dec_g2_rows(codec.enc_g2_rows(pk_rows))
    assert [isinstance(r, PackedRow) for r in packed_sigs] \
        == [True, True, False, True, True, True]
    backend = JaxSigBackend(mesh_devices=2)
    lay = backend._layout
    bucket, width = lay.mesh_bucket(6), marshal.committee_width(sig_rows,
                                                                pk_rows)
    calls = []

    def step(*args):
        calls.append(args)
        return lay.place(np.zeros(bucket, bool)), np.int32(0)

    key = (bucket, width, backend._wire, "recompute")
    backend._mesh_exec[key], backend._mesh_collectives[key] = step, 1
    for sigs, pks in ((sig_rows, pk_rows), (packed_sigs, packed_pks)):
        assert backend.bls_verify_committees(messages, sigs, pks) \
            == [False] * 6
    listed, packed = calls
    assert len(listed) == len(packed) == 9
    for a, b in zip(listed, packed):
        assert len(b.sharding.device_set) == 2
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # sx is the signatures' plane: 6 of `bucket` rows hold points
    assert np.asarray(packed[4]).any(axis=1).tolist() \
        == [True, False, True, True, True, True] + [False] * (bucket - 6)


# -- the tri-layout dispatch workloads (slow tier: pairing compiles) -------


@pytest.fixture(scope="module")
def backends():
    import jax

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual mesh (tests/conftest.py)")
    from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend

    return {n: JaxSigBackend(mesh_devices=n) for n in (1, 2, 8)}


@functools.lru_cache(maxsize=1)
def _committee_cols():
    """6 committees of width 3 with every interesting row class: valid,
    EMPTY (a rejection: an empty committee proves nothing), an absent
    voter encoded as INFINITY slots in both the sig and pk rows (still
    verifies via the remaining signers), and a forged row."""
    rows, width = 6, 3
    messages, sig_rows, pk_rows, keys = [], [], [], []
    for i in range(rows):
        msg = bytes([11, i]) * 16
        sigs, pks = [], []
        for j in range(width):
            sk, pk = bls.bls_keygen(bytes([i + 1, j + 1, 29]) * 8)
            sigs.append(bls.bls_sign(msg, sk))
            pks.append(pk)
        messages.append(msg)
        sig_rows.append(sigs)
        pk_rows.append(pks)
        keys.append(f"mesh-row:{i}")
    sig_rows[1], pk_rows[1] = [], []  # empty committee -> False
    sig_rows[2][1] = None  # absent voter: infinity in BOTH halves
    pk_rows[2][1] = None   # -> the other two signers still verify
    forged_sk, _ = bls.bls_keygen(bytes([5, 1, 29]) * 8)  # row 4 voter 0
    sig_rows[4][0] = bls.bls_sign(b"some other collation header!!!!!",
                                  forged_sk)
    return messages, sig_rows, pk_rows, keys


@functools.lru_cache(maxsize=1)
def _committee_want():
    messages, sig_rows, pk_rows, _ = _committee_cols()
    want = PythonSigBackend().bls_verify_committees(messages, sig_rows,
                                                    pk_rows)
    assert want == [True, False, True, True, False, True]
    return want


@functools.lru_cache(maxsize=1)
def _poly_cols():
    """Multiproof rows in wire form: honest multi- and single-index
    openings, a tampered eval, the EMPTY index set, truncated proof
    bytes, and the degenerate constant-polynomial row whose proof is
    the G1 INFINITY (must still verify True)."""
    from gethsharding_tpu.das import pcs

    rows = []
    for seed, n, indices in ((21, 6, (0, 2, 5)), (22, 5, (1,))):
        values = [random.Random(seed).randrange(pcs.N) for _ in range(n)]
        proof, evals = pcs.open_multi(values, indices)
        rows.append((pcs.g1_to_bytes(pcs.commit(values)), list(indices),
                     evals, pcs.g1_to_bytes(proof), n))
    good = rows[0]
    evals = good[2]
    rows.append((good[0], good[1],
                 [evals[0], (evals[1] + 1) % pcs.N, evals[2]],
                 good[3], good[4]))                      # tampered eval
    rows.append((good[0], [], [], good[3], good[4]))     # empty index set
    rows.append((good[0], good[1], evals, good[3][:32],
                 good[4]))                               # short proof
    const = [42] * 4
    c_proof, c_evals = pcs.open_multi(const, (0, 2))
    rows.append((pcs.g1_to_bytes(pcs.commit(const)), [0, 2], c_evals,
                 pcs.g1_to_bytes(c_proof), 4))           # infinity proof
    return tuple(tuple(col) for col in zip(*rows))


@pytest.mark.slow
def test_committee_tri_layout_bit_identity(backends):
    messages, sig_rows, pk_rows, keys = _committee_cols()
    want = _committee_want()
    for n, backend in sorted(backends.items()):
        got = backend.bls_verify_committees(messages, sig_rows, pk_rows,
                                            pk_row_keys=keys)
        assert got == want, f"{n}-device sync verdicts diverge"
        fut = backend.bls_verify_committees_async(
            messages, sig_rows, pk_rows, pk_row_keys=keys)
        assert not fut.done()  # staged, not pulled
        assert fut.result() == want, f"{n}-device async verdicts diverge"
        # the rows as they arrive off the wire: packed, under new keys
        # (the row with the absent voter stays a list)
        got = backend.bls_verify_committees(
            messages, codec.dec_g1_rows(codec.enc_g1_rows(sig_rows)),
            codec.dec_g2_rows(codec.enc_g2_rows(pk_rows)),
            pk_row_keys=[k + ":packed" for k in keys])
        assert got == want, f"{n}-device packed verdicts diverge"
    # the single-device layout never reports mesh evidence
    assert backends[1].last_mesh is None


@pytest.mark.slow
def test_committee_mesh_non_vacuity(backends):
    """The pjit path really sharded: verdict plane on every device,
    exactly ONE collective (the vote-total psum) in the compiled step,
    vote total agreeing with the verdict plane it reduced."""
    messages, sig_rows, pk_rows, keys = _committee_cols()
    want = _committee_want()
    for n in (2, 8):
        backend = backends[n]
        fut = backend.bls_verify_committees_async(
            messages, sig_rows, pk_rows, pk_row_keys=keys)
        info = backend.last_mesh
        assert info["op"] == "bls_verify_committees"
        assert info["n_devices"] == n
        assert info["collectives"] == 1, (
            f"{n}-device step must psum ONCE, counted from the AOT HLO")
        assert info["vote_total"] is None  # not finalized yet
        assert fut.result() == want
        assert info["verdict_devices"] == n
        assert info["vote_total"] == sum(want)
        # the memoized planes are themselves mesh-sharded arrays: the
        # line table under precomp (the default), the pk planes on the
        # recompute path
        memo = (backend._mesh_line_memo if backend._precomp
                else backend._mesh_memo)
        assert len(memo[1][0].sharding.device_set) == n


@pytest.mark.slow
def test_multiproofs_tri_layout_bit_identity(backends):
    cols = _poly_cols()
    want = get_backend("python").das_verify_multiproofs(
        *[list(col) for col in cols])
    assert want == [True, True, False, False, False, True]
    for n, backend in sorted(backends.items()):
        got = backend.das_verify_multiproofs(*[list(col) for col in cols])
        assert got == want, f"{n}-device multiproof verdicts diverge"
        if n == 1:
            continue
        info = backend.last_mesh
        assert info["op"] == "das_verify_multiproofs"
        assert info["collectives"] == 0  # per-row work: nothing crosses
        assert info["verdict_devices"] == n


@pytest.mark.slow
def test_mesh_empty_batches(backends):
    backend = backends[2]
    assert backend.das_verify_multiproofs([], [], [], [], []) == []
    assert backend.last_wire is None
    assert backend.bls_verify_committees([], [], []) == []
    assert backend.last_wire is None and backend.last_mesh is None


@pytest.mark.slow
def test_mesh_cache_shard_eviction_churn(backends):
    """Starve the per-device shards (1-byte budgets): every keyed
    insert immediately evicts, verdicts stay bit-identical, and the
    shards end EMPTY — churn must never corrupt or grow."""
    backend = backends[2]
    messages, sig_rows, pk_rows, _ = _committee_cols()
    want = _committee_want()
    shards = backend._mesh_shards
    budgets = [s.budget for s in shards]
    evict0 = [s.m_evict.value for s in shards]
    miss0 = [s.m_miss.value for s in shards]
    try:
        for s in shards:
            s.budget = 1
        for rnd in range(3):
            # fresh keys each round: misses the batch memo AND the
            # starved LRUs, so every round re-inserts and re-evicts
            keys = [f"churn{rnd}:{i}" for i in range(len(messages))]
            got = backend.bls_verify_committees(
                messages, sig_rows, pk_rows, pk_row_keys=keys)
            assert got == want, f"round {rnd} verdicts diverge under churn"
    finally:
        for s, budget in zip(shards, budgets):
            s.budget = budget
        with backend._mesh_lock:
            backend._mesh_memo = None
    for i, s in enumerate(shards):
        assert s.m_evict.value > evict0[i], f"shard{i} never evicted"
        assert s.m_miss.value > miss0[i], f"shard{i} never missed"
        assert not s.cache and s.bytes == 0, (
            f"shard{i} retained entries past a 1-byte budget")


@pytest.mark.slow
def test_mesh_shard_owners_disjoint(backends):
    """Every mesh slot registers its own devscope census owner, and
    ownership is DISJOINT: no device buffer is attributed twice."""
    from gethsharding_tpu import devscope

    backend = backends[8]
    messages, sig_rows, pk_rows, keys = _committee_cols()
    backend.bls_verify_committees(messages, sig_rows, pk_rows,
                                  pk_row_keys=keys)
    registered = set(devscope.owners())
    for i in range(8):
        assert f"pk_plane_lru_shard{i}" in registered
    buf_ids = [set(map(id, backend._mesh_shard_buffers(i)))
               for i in range(8)]
    for i in range(8):
        assert buf_ids[i], f"shard{i} owns no buffers after a dispatch"
        for j in range(i + 1, 8):
            assert not (buf_ids[i] & buf_ids[j]), (
                f"shards {i} and {j} both claim a buffer")
    assert sum(backend._mesh_claimed_bytes(i) for i in range(8)) > 0
