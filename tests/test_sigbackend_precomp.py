"""Fixed-base pairing precomputation (ISSUE 19).

Differential coverage of the `GETHSHARDING_PRECOMP` path: Miller-loop
line tables resident in the device LRU, keyed by `pk_row_key`, consumed
by the precomp committee kernel instead of re-running the
fixed-argument point arithmetic every dispatch.

- precompute-vs-recompute BIT-IDENTITY over randomized committees ×
  empty rows × infinity slots × cancelled (infinity-aggregate) pk rows
  × the u16 wire × sync/async — every verdict pinned to
  `PythonSigBackend`;
- LRU eviction churn of line tables under a starvation budget (tables
  evict, verdicts hold, accounting stays bounded);
- the small-fix regression: line tables charged at TRUE dtype-width
  bytes, so the cache's claimed accounting equals the byte-for-byte
  buffer census exactly (devscope's 5%+64KiB drift gate stays quiet);
- non-vacuity via compiled-HLO op census (`count_ops`, the PR-18
  collective-count idiom): the precomp executable must carry far fewer
  `multiply` ops than its recompute twin;
- tri-layout (1/2/8-device mesh) bit-identity with per-shard line
  tables, one collective per step, and disjoint shard ownership.

Host-only policy tests stay in the fast tier; everything compiling a
pairing kernel at a NEW shape is marked `slow` (the fast-tier dispatch
tests reuse the resident suite's bucket-4 shapes, warm in the
persistent compile cache).
"""

import functools
import random

import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.sigbackend import JaxSigBackend, get_backend
from gethsharding_tpu.sigbackend.layout import count_ops

# one shared key pool: rows drawn from it recur across rounds, so the
# line-table LRU sees hits, misses AND churn under a tiny byte budget
KEYPOOL = [bls.bls_keygen(b"pre-pool-%d" % i) for i in range(8)]


def _rand_round(rng, n_rows=4, max_k=3):
    """One randomized batch: (msgs, sig_rows, pk_rows, row_keys).

    Rows cover empty committees, infinity (None) signature/pubkey
    slots, tampered signatures, pk rows CANCELLED to the infinity
    aggregate (pk + (-pk) — the table must be the infinity-marked
    rejection, never a stale accept), and honest rows. Shapes stay
    inside one compile bucket (n_rows=4, width<=4). Row keys derive
    from the pk row CONTENT (member set + transform marker) — the
    caller contract that keys uniquely determine the row's points."""
    msgs, sig_rows, pk_rows, keys = [], [], [], []
    for _ in range(n_rows):
        kind = rng.random()
        tag = b"pre-msg-%d" % rng.randrange(6)
        if kind < 0.12:
            msgs.append(tag)
            sig_rows.append([])
            pk_rows.append([])
            keys.append(None)
            continue
        k = rng.randrange(1, max_k + 1)
        members = rng.sample(range(len(KEYPOOL)), k)
        sigs = [bls.bls_sign(tag, KEYPOOL[i][0]) for i in members]
        pks = [KEYPOOL[i][1] for i in members]
        mark = "plain"
        if kind < 0.26 and k >= 2:
            sigs[0] = None  # infinity signature slot (skipped, both paths)
            mark = "isig"
        elif kind < 0.40 and k >= 2:
            pks[1] = None  # infinity pubkey slot
            mark = "ipk"
        elif kind < 0.54:
            sigs[-1] = bls.bls_sign(b"tampered", KEYPOOL[members[-1]][0])
            mark = "forged"  # pk row unchanged; marker only aids debug
        elif kind < 0.68 and k >= 2:
            pks = [pks[0], bls.g2_neg(pks[0])] + pks[2:]
            mark = "cancel"  # pk aggregate = infinity -> reject
        msgs.append(tag)
        sig_rows.append(sigs)
        pk_rows.append(pks)
        keys.append((tuple(members), mark,
                     tuple(i for i, p in enumerate(pks) if p is None)))
    return msgs, sig_rows, pk_rows, keys


# -- flag + policy (host-only, fast tier) ----------------------------------


def test_precomp_flag_validation(monkeypatch):
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "yes")
    with pytest.raises(ValueError):
        JaxSigBackend()
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "0")
    off = JaxSigBackend()
    assert off._precomp is False
    # flag off: no generator table is shipped at construction
    assert off._gen_lines_dev is None and off._gen_lines_mesh is None
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "1")
    on = JaxSigBackend()
    assert on._precomp is True  # the default
    assert on._gen_lines_dev is not None


def test_count_ops_on_hlo_text():
    hlo = """\
ENTRY main {
  %m = f32[8]{0} multiply(%a, %b)
  %s = f32[8]{0} add(%a, %b)
  %m2 = f32[8]{0} multiply(%m, %s)
}
"""
    assert count_ops(hlo, "multiply") == 2
    assert count_ops(hlo, "add") == 1
    assert count_ops("", "multiply") == 0


# -- single-device dispatch differentials (resident-suite shapes) ----------


# the u16 wire compiles its own programs (tens of seconds cold on
# XLA:CPU): it runs in the full suite, the default wire in the fast tier
@pytest.mark.parametrize(
    "wire", ["i32", pytest.param("u16", marks=pytest.mark.slow)])
def test_randomized_precomp_parity_sync_async(monkeypatch, wire):
    """Randomized rounds: sync and async precomp verdicts match the
    scalar backend bit-for-bit, across the wire dtypes, with the
    precomp path engaged (line tables, not pk planes)."""
    if wire == "u16":
        monkeypatch.setenv("GETHSHARDING_TPU_WIRE", "u16")
    else:
        monkeypatch.delenv("GETHSHARDING_TPU_WIRE", raising=False)
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "1")
    backend = JaxSigBackend()
    assert backend._precomp
    py = get_backend("python")
    rng = random.Random(777 if wire == "i32" else 778)
    for _ in range(3):
        msgs, sig_rows, pk_rows, keys = _rand_round(rng)
        want = py.bls_verify_committees(msgs, sig_rows, pk_rows)
        sync = backend.bls_verify_committees(
            msgs, sig_rows, pk_rows, pk_row_keys=keys)
        future = backend.bls_verify_committees_async(
            msgs, sig_rows, pk_rows, pk_row_keys=keys)
        assert sync == future.result() == want
        assert backend.last_wire["precomp"] is True


def _forged_empty_ragged_round():
    """One batch inside the suite's compile bucket (4 rows, width <= 4)
    with the three rows a period audit must not get wrong: a forged
    signature under honest keys, an empty committee, and ragged rows
    (1, 2 and 3 votes) whose pad slots the masks must hide."""
    def row(tag, members, forge=False):
        sigs = [bls.bls_sign(tag, KEYPOOL[i][0]) for i in members]
        if forge:
            sigs[-1] = bls.bls_sign(b"forged", KEYPOOL[members[-1]][0])
        return (tag, sigs, [KEYPOOL[i][1] for i in members],
                (tuple(members), "forged" if forge else "plain", ()))

    rows = [row(b"fer-0", [0, 1, 2]), row(b"fer-1", [3, 4], forge=True),
            (b"fer-2", [], [], None), row(b"fer-3", [5])]
    return tuple(list(col) for col in zip(*rows))


@pytest.mark.parametrize("path", ["recompute", "table_fed"])
def test_forged_empty_ragged_rows_match_scalar(monkeypatch, path):
    """Under the default limb product (the padded-row sum, PR 29) the
    recompute kernel (no row keys sent) and the table-fed verify (row
    keys sent) both return the scalar backend's verdicts on a forged,
    an empty and three ragged rows: True, False, False, True."""
    monkeypatch.delenv("GETHSHARDING_TPU_WIRE", raising=False)
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "1")
    backend = JaxSigBackend()
    msgs, sig_rows, pk_rows, keys = _forged_empty_ragged_round()
    want = get_backend("python").bls_verify_committees(
        msgs, sig_rows, pk_rows)
    assert want == [True, False, False, True]
    sent = keys if path == "table_fed" else None
    got = backend.bls_verify_committees(msgs, sig_rows, pk_rows,
                                        pk_row_keys=sent)
    assert got == want
    assert backend.last_wire["precomp"] is (path == "table_fed")


def _keyed_rows(n_rows):
    """`n_rows` keyed rows, every one three slots wide (width bucket 4,
    the suite's), dealt in turn from the four kinds a period audit must
    not get wrong: a ragged row (an absent voter's two infinity slots),
    a forged vote under honest keys, an empty committee (no vote in any
    slot) and a full row."""
    def row(i):
        kind = ("ragged", "forged", "empty", "full")[i % 4]
        tag = b"one-program-%d" % i
        members = [(i + j) % len(KEYPOOL) for j in range(3)]
        sigs = [bls.bls_sign(tag, KEYPOOL[m][0]) for m in members]
        pks = [KEYPOOL[m][1] for m in members]
        if kind == "ragged":
            sigs[1] = pks[1] = None
        elif kind == "forged":
            sigs[-1] = bls.bls_sign(b"forged", KEYPOOL[members[-1]][0])
        elif kind == "empty":
            sigs, pks = [None] * 3, [None] * 3
        return tag, sigs, pks, (tuple(members), kind)

    return tuple(list(col) for col in zip(*(row(i) for i in range(n_rows))))


@pytest.mark.parametrize("bucket", [1, 4, 8])
def test_keyed_dispatch_is_one_program_over_the_bucket(monkeypatch, bucket):
    """A keyed dispatch launches ONE table-fed program over the whole
    bucket (PR 31: no lane blocks, nothing chosen by a variable): one
    call of the jitted kernel with the bucket as its leading axis, one
    counted compile under a shape key of (bucket, width, wire), no
    `blocks` in the wire ledger; and its verdicts on ragged, forged,
    empty and full rows are the keyless recompute call's and the scalar
    backend's, bit for bit."""
    from gethsharding_tpu import devscope

    monkeypatch.delenv("GETHSHARDING_TPU_WIRE", raising=False)
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "1")
    heard = []
    monkeypatch.setattr(devscope.COMPILES, "after_compile",
                        lambda op, shape: heard.append((op, shape)))
    backend = JaxSigBackend()
    launched = []
    full = backend._precomp_full

    def counted(*args):
        launched.append(args[0].shape[0])
        return full(*args)

    backend._precomp_full = counted
    # 1, 3 and 7 rows: the last two leave the bucket a padded row
    msgs, sig_rows, pk_rows, keys = _keyed_rows(max(1, bucket - 1))
    assert JaxSigBackend._bucket(len(msgs)) == bucket
    want = get_backend("python").bls_verify_committees(
        msgs, sig_rows, pk_rows)
    assert want == [kind in ("full", "ragged") for _, kind in keys]
    for _ in range(2):  # cold (tables precomputed), then warm
        assert backend.bls_verify_committees(
            msgs, sig_rows, pk_rows, pk_row_keys=keys) == want
        assert backend.last_wire["precomp"] is True
        assert "blocks" not in backend.last_wire
    assert launched == [bucket, bucket]
    assert [shape for op, shape in heard
            if op == "bls_committee_precomp"] == [(bucket, 4, "i32")]
    assert {key for key in backend._shape_seen
            if key[0] == "bls_committee_precomp"} \
        == {("bls_committee_precomp", bucket, 4, "i32")}
    # the keyless call takes the recompute kernel: the same verdicts
    assert backend.bls_verify_committees(msgs, sig_rows, pk_rows) == want
    assert backend.last_wire["precomp"] is False
    assert launched == [bucket, bucket]


@functools.lru_cache(maxsize=1)
def _reference_precompute():
    """The precompute as the kernel module gives it, one (B, ...) table
    plane and one (B,) flag plane: no cut, no stack."""
    import jax

    from gethsharding_tpu.ops import bn256_jax

    return jax.jit(bn256_jax.precompute_g2_lines)


def _assembly_batch(case):
    """(resident, batch, plan) of one assembly case, every batch four
    rows of `_keyed_rows` (bucket 4, width 4). `resident` is dispatched
    first to fill the LRU (None: nothing); `plan` names what each row
    of `batch` must then be: "miss", "hit" or "zero"."""
    msgs, sig_rows, pk_rows, keys = _keyed_rows(4)
    first = (msgs, sig_rows, pk_rows, keys)

    def rekeyed(plan):
        cols = [list(col) for col in first]
        for i, kind in enumerate(plan):
            if kind == "miss":
                cols[3][i] = (case,) + keys[i]
            elif kind == "zero":
                cols[1][i], cols[2][i], cols[3][i] = [], [], None
        return tuple(cols), plan

    if case == "all_miss":      # four misses: the precompute's bucket 4
        return (None,) + rekeyed(["miss"] * 4)
    if case == "all_hit":       # by key: the test clears the line memo
        return (first,) + rekeyed(["hit"] * 4)
    if case == "three_miss":    # three misses share the bucket of four
        return (first,) + rekeyed(["miss", "hit", "miss", "miss"])
    if case == "mixed":         # zero, hit and miss, no kind together
        return (first,) + rekeyed(["miss", "zero", "hit", "miss"])
    raise ValueError(case)


@pytest.mark.parametrize("case",
                         ["all_miss", "all_hit", "three_miss", "mixed"])
def test_assembled_tables_are_the_precomputes_bit_for_bit(monkeypatch, case):
    """What the two launches hand the table-fed verify (PR 34: the cut
    inside the precompute's program, the jitted stack) is, bit for bit,
    `np.stack` of the per-row tables the kernel module's precompute
    gives, with the zero table and a set flag on an empty row; for a
    plan of misses alone, of hits alone (by key, the line memo
    cleared), of three misses in the bucket of four and of zero, hit
    and miss in no order. The verdicts are the keyless
    recompute call's and the scalar backend's."""
    import numpy as np

    monkeypatch.delenv("GETHSHARDING_TPU_WIRE", raising=False)
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "1")
    backend = JaxSigBackend()
    resident, batch, plan = _assembly_batch(case)
    if resident is not None:
        backend.bls_verify_committees(*resident[:3], pk_row_keys=resident[3])
        with backend._pk_dev_lock:
            backend._pk_line_memo = None    # hits go by key, not by memo
    assembled, plans = [], []
    line_tables = backend._line_tables

    def captured(st):
        plans.append([step[0] for step in st["line_plan"]])
        assembled.append(line_tables(st))
        return assembled[-1]

    backend._line_tables = captured
    misses = metrics.counter("jax/pk_device_cache/misses")
    before = misses.value
    msgs, sig_rows, pk_rows, keys = batch
    got = backend.bls_verify_committees(msgs, sig_rows, pk_rows,
                                        pk_row_keys=keys)
    assert plans == [plan]
    assert misses.value - before == plan.count("miss")
    want = get_backend("python").bls_verify_committees(
        msgs, sig_rows, pk_rows)
    assert got == want
    assert backend.bls_verify_committees(msgs, sig_rows, pk_rows) == want
    tab, inf, g2_bytes = assembled[0]
    assert (g2_bytes > 0) == ("miss" in plan)
    px, py, pm = backend._pk_rows_to_limbs(pk_rows, 4)
    ref_tab, ref_inf = (np.array(a)
                        for a in _reference_precompute()(px, py, pm))
    for i, kind in enumerate(plan):
        if kind == "zero":
            ref_tab[i], ref_inf[i] = 0, True
    assert ref_tab.any() and not ref_inf.all()
    assert tab.dtype == ref_tab.dtype and inf.dtype == ref_inf.dtype
    assert np.array_equal(np.asarray(tab), ref_tab)
    assert np.array_equal(np.asarray(inf), ref_inf)


def test_a_miss_dispatch_is_two_launches_and_no_eager_operation(monkeypatch):
    """Between the miss planes' staging and the table-fed verify's
    launch a table is touched by two jitted programs and by nothing
    else (PR 34): `_line_tables` calls the precompute once and the
    stack once, and binds NO primitive eagerly (the hook:
    `jax._src.core.EvalTrace.process_primitive`, through which every
    eager operation runs and a jitted call does not), on a backend's
    first dispatch and on a later one; a memo hit launches neither."""
    import jax.numpy as jnp
    from jax._src import core

    monkeypatch.delenv("GETHSHARDING_TPU_WIRE", raising=False)
    monkeypatch.setenv("GETHSHARDING_PRECOMP", "1")
    backend = JaxSigBackend()
    launched, eager, listening = [], [], []
    for name in ("_precompute", "_stack_lines"):
        def counted(*args, _fn=getattr(backend, name), _name=name):
            launched.append(_name)
            return _fn(*args)

        setattr(backend, name, counted)
    process_primitive = core.EvalTrace.process_primitive

    def heard(self, primitive, args, params):
        if listening:
            eager.append(primitive.name)
        return process_primitive(self, primitive, args, params)

    monkeypatch.setattr(core.EvalTrace, "process_primitive", heard)
    line_tables = backend._line_tables

    def listened(st):
        listening.append(True)
        try:
            return line_tables(st)
        finally:
            listening.pop()

    backend._line_tables = listened
    # the hook hears what the old path did: an eager index and a stack
    listening.append(True)
    rows = jnp.zeros((2, 3), jnp.int32)
    jnp.stack([rows[0], rows[1]])
    listening.pop()
    assert {"concatenate", "squeeze"} <= set(eager)
    del eager[:]
    msgs, sig_rows, pk_rows, keys = _keyed_rows(4)
    want = [kind in ("full", "ragged") for _, kind in keys]
    for stamp in (0, 1):    # the backend's first dispatch, and a later
        fresh = [(stamp,) + key for key in keys]
        assert backend.bls_verify_committees(
            msgs, sig_rows, pk_rows, pk_row_keys=fresh) == want
        assert launched == ["_precompute", "_stack_lines"] * (stamp + 1)
    assert backend.bls_verify_committees(
        msgs, sig_rows, pk_rows, pk_row_keys=fresh) == want  # the memo
    assert backend.last_wire["g2_wire_bytes"] == 0
    assert launched == ["_precompute", "_stack_lines"] * 2
    assert eager == []


def test_no_reader_of_the_block_variable_is_left():
    """The lane-block variable went with the pipeline (PR 31): its name
    stands in no file of the package, the tests, the benchmark, the
    scripts, `chip_smoke.py` or the README."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    name = "GETHSHARDING_" + "PRECOMP_" + "BLOCKS"
    files = [root / "chip_smoke.py", root / "README.md"]
    for sub in ("gethsharding_tpu", "tests", "benchmark", "scripts"):
        files += [f for f in (root / sub).rglob("*")
                  if f.is_file() and f.suffix in
                  (".py", ".md", ".json", ".sh", ".toml", ".txt")]
    assert len(files) > 200  # the walk found the tree
    assert [str(f.relative_to(root)) for f in files
            if name in f.read_text(errors="replace")] == []


def test_warm_line_tables_ship_zero_g2_bytes():
    """The steady-state precomp shape: cold pays ONE precompute
    dispatch and ships the miss rows' pk planes; warm ships ZERO G2
    bytes — the table hit replaces even the pk-plane transfer the
    recompute-resident path would take."""
    backend = JaxSigBackend()  # defaults: resident on, precomp on
    assert backend._precomp
    rng = random.Random(42)
    msgs, sig_rows, pk_rows, keys = _rand_round(rng)
    while not any(pk_rows):  # need at least one pointful row
        msgs, sig_rows, pk_rows, keys = _rand_round(rng)
    want = get_backend("python").bls_verify_committees(
        msgs, sig_rows, pk_rows)
    cold = backend.bls_verify_committees(
        msgs, sig_rows, pk_rows, pk_row_keys=keys)
    assert cold == want
    assert backend.last_wire["precomp"] is True
    assert backend.last_wire["g2_wire_bytes"] > 0
    warm = backend.bls_verify_committees(
        msgs, sig_rows, pk_rows, pk_row_keys=keys)
    assert warm == want
    assert backend.last_wire["g2_wire_bytes"] == 0
    assert (backend.last_wire["pk_hit_rows"]
            == backend.last_wire["pk_rows"]
            == sum(1 for r in pk_rows if r))
    # a SHORT key list marks trailing rows uncached, not dropped: the
    # unkeyed pointful rows precompute per dispatch, verdict unchanged
    assert backend.bls_verify_committees(
        msgs, sig_rows, pk_rows, pk_row_keys=keys[:1]) == want
    assert backend.last_wire["precomp"] is True
    # keyless dispatch: residency (and so precomp) disengages — the
    # recompute path answers, bit-identical
    assert backend.bls_verify_committees(msgs, sig_rows, pk_rows) == want
    assert backend.last_wire["precomp"] is False


def test_line_table_eviction_churn(monkeypatch):
    """Fresh keys every round under a ~2 KB budget: every line-table
    insert immediately evicts (a table alone is ~50 KB), verdicts stay
    bit-identical, the byte accounting respects the budget."""
    monkeypatch.setenv("GETHSHARDING_TPU_RESIDENT_MB", "0.002")
    backend = JaxSigBackend()
    assert backend._precomp
    py = get_backend("python")
    evictions = metrics.counter("jax/pk_device_cache/evictions")
    before = evictions.value
    rng = random.Random(1357)
    for rnd in range(3):
        msgs, sig_rows, pk_rows, keys = _rand_round(rng)
        keys = [None if k is None else (rnd,) + k for k in keys]
        want = py.bls_verify_committees(msgs, sig_rows, pk_rows)
        got = backend.bls_verify_committees(
            msgs, sig_rows, pk_rows, pk_row_keys=keys)
        assert got == want, f"round {rnd} verdicts diverge under churn"
    assert evictions.value > before
    assert backend._pk_dev_bytes <= backend._resident_budget


def test_line_table_bytes_are_true_dtype_width(monkeypatch):
    """The ISSUE-19 small fix: line tables are charged at their TRUE
    int32 byte width, not a pk-plane-shape estimate — the cache's own
    accounting must equal the byte-for-byte census of every buffer it
    owns EXACTLY (u16 wire especially: pk planes narrow to u16 while
    tables stay i32), so devscope's claimed-vs-census drift gate
    (5%+64KiB) stays quiet on precomp-heavy workloads."""
    monkeypatch.setenv("GETHSHARDING_TPU_WIRE", "u16")
    backend = JaxSigBackend()
    assert backend._precomp
    rng = random.Random(99)
    msgs, sig_rows, pk_rows, keys = _rand_round(rng)
    while not any(pk_rows):
        msgs, sig_rows, pk_rows, keys = _rand_round(rng)
    want = get_backend("python").bls_verify_committees(
        msgs, sig_rows, pk_rows)
    for _ in range(2):  # cold (insert) + warm (memo) both censused
        assert backend.bls_verify_committees(
            msgs, sig_rows, pk_rows, pk_row_keys=keys) == want
    claimed = backend._resident_claimed_bytes()
    actual = sum(int(b.nbytes) for b in backend._resident_buffers())
    assert claimed == actual > 0, (
        f"resident accounting drifted from the buffer census: "
        f"claimed={claimed} actual={actual}")
    # and the devscope census agrees: the registered owner shows no
    # drift (this instance is the latest registrant of pk_plane_lru;
    # a throwaway poller walks the real live buffers — no boot() needed)
    from gethsharding_tpu.devscope.memory import MemoryPoller

    entry = MemoryPoller(interval_s=60).census()["owners"].get(
        "pk_plane_lru")
    assert entry is not None
    assert not entry.get("drifted"), entry


# -- non-vacuity: the compiled-HLO op census (slow: new AOT shape) ---------


@pytest.mark.slow
def test_precomp_hlo_census_drops_point_arithmetic():
    """The warm path really skips the dbl/madd point arithmetic: the
    AOT-compiled precomp executable carries far fewer `multiply` ops
    than the recompute twin at the same shape (same idiom as the mesh
    suite's collective count — optimized HLO text, no hand-claims)."""
    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.ops import bn256_jax as k

    nl = k.NLIMBS
    steps = k.LINE_TABLE_SHAPE[0]
    b, w = 1, 2
    z32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    pre_args = (z32((b, nl)), z32((b, nl)),
                z32((b, w, nl)), z32((b, w, nl)), jnp.zeros((b, w), bool),
                z32((b, steps, 3, 2, nl)),
                jnp.zeros((b,), bool), jnp.zeros((b,), bool))
    rec_args = (z32((b, nl)), z32((b, nl)),
                z32((b, w, nl)), z32((b, w, nl)), jnp.zeros((b, w), bool),
                z32((b, w, 2, nl)), z32((b, w, 2, nl)),
                jnp.zeros((b, w), bool), jnp.zeros((b,), bool))
    pre_mul = count_ops(jax.jit(k.bls_verify_committee_precomp_batch)
                        .lower(*pre_args).compile().as_text(), "multiply")
    rec_mul = count_ops(jax.jit(k.bls_aggregate_verify_committee_batch)
                        .lower(*rec_args).compile().as_text(), "multiply")
    assert 0 < pre_mul < 0.7 * rec_mul, (
        f"precomp executable must drop the fixed-argument point "
        f"arithmetic: {pre_mul} multiplies vs recompute {rec_mul}")


# -- tri-layout mesh differentials (slow: mesh pairing compiles) -----------


@pytest.fixture(scope="module")
def mesh_backends():
    import jax

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual mesh (tests/conftest.py)")
    from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend as B

    return {n: B(mesh_devices=n) for n in (1, 2, 8)}


@functools.lru_cache(maxsize=1)
def _mesh_cols():
    """8 committees (one per 8-device mesh slot): honest rows plus an
    empty committee, an absent voter (infinity slots), a forged vote,
    and a pk aggregate cancelled to infinity."""
    rows, width = 8, 3
    messages, sig_rows, pk_rows, keys = [], [], [], []
    for i in range(rows):
        msg = bytes([23, i]) * 16
        sigs, pks = [], []
        for j in range(width):
            sk, pk = bls.bls_keygen(bytes([i + 1, j + 1, 41]) * 8)
            sigs.append(bls.bls_sign(msg, sk))
            pks.append(pk)
        messages.append(msg)
        sig_rows.append(sigs)
        pk_rows.append(pks)
        keys.append(f"pre-mesh:{i}")
    sig_rows[1], pk_rows[1] = [], []  # empty committee -> False
    sig_rows[2][1] = None  # absent voter: infinity in BOTH halves
    pk_rows[2][1] = None   # -> the other two signers still verify
    forged_sk, _ = bls.bls_keygen(bytes([6, 2, 41]) * 8)
    sig_rows[4][0] = bls.bls_sign(b"some other collation header!!!!!",
                                  forged_sk)
    pk_rows[6] = [pk_rows[6][0], bls.g2_neg(pk_rows[6][0])]  # cancelled
    sig_rows[6] = sig_rows[6][:2]
    return messages, sig_rows, pk_rows, keys


@functools.lru_cache(maxsize=1)
def _mesh_want():
    messages, sig_rows, pk_rows, _ = _mesh_cols()
    want = get_backend("python").bls_verify_committees(
        messages, sig_rows, pk_rows)
    assert want == [True, False, True, True, False, True, False, True]
    return want


@pytest.mark.slow
def test_precomp_tri_layout_bit_identity(mesh_backends):
    messages, sig_rows, pk_rows, keys = _mesh_cols()
    want = _mesh_want()
    for n, backend in sorted(mesh_backends.items()):
        assert backend._precomp, f"{n}-device backend must default on"
        got = backend.bls_verify_committees(messages, sig_rows, pk_rows,
                                            pk_row_keys=keys)
        assert got == want, f"{n}-device sync verdicts diverge"
        fut = backend.bls_verify_committees_async(
            messages, sig_rows, pk_rows, pk_row_keys=keys)
        assert fut.result() == want, f"{n}-device async verdicts diverge"
        assert backend.last_wire["precomp"] is True
        if n > 1:
            info = backend.last_mesh
            assert info["precomp"] is True
            assert info["collectives"] == 1, (
                f"{n}-device precomp step must psum ONCE: {info}")
            assert info["verdict_devices"] == n
            assert info["vote_total"] == sum(want)


@pytest.mark.slow
def test_precomp_mesh_warm_zero_g2_and_disjoint_shards(mesh_backends):
    """Warm mesh dispatch: line tables hit in every per-device shard
    (zero G2 bytes), and shard buffer ownership — tables included —
    stays pairwise DISJOINT under the per-shard census owners."""
    backend = mesh_backends[8]
    messages, sig_rows, pk_rows, keys = _mesh_cols()
    want = _mesh_want()
    for _ in range(2):
        assert backend.bls_verify_committees(
            messages, sig_rows, pk_rows, pk_row_keys=keys) == want
    assert backend.last_wire["precomp"] is True
    assert backend.last_wire["g2_wire_bytes"] == 0
    buf_ids = [set(map(id, backend._mesh_shard_buffers(i)))
               for i in range(8)]
    for i in range(8):
        assert buf_ids[i], f"shard{i} owns no buffers after a dispatch"
        for j in range(i + 1, 8):
            assert not (buf_ids[i] & buf_ids[j]), (
                f"shards {i} and {j} both claim a buffer")
    assert sum(backend._mesh_claimed_bytes(i) for i in range(8)) > 0
