"""Device-resident pk planes + the async committee path (ISSUE 4).

Randomized differential test against the scalar backend over the full
matrix: empty rows, infinity (None) points inside rows, row-key churn
forcing memory-accounted eviction, the u16 wire on and off, and the
sync vs async (overlapped) dispatch path — every verdict pinned
byte-identical to `PythonSigBackend`. Plus the steady-state ledger
claim the perf work rests on: a warm device cache ships ZERO G2 pubkey
bytes per dispatch, and the notary's overlapped `audit_periods`
pipeline returns exactly the batched form's results.
"""

import random

import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.sigbackend import JaxSigBackend, get_backend

# one shared key pool: rows drawn from it recur across rounds, so the
# device cache sees hits, misses AND churn under a tiny byte budget
KEYPOOL = [bls.bls_keygen(b"res-pool-%d" % i) for i in range(8)]


def _rand_round(rng, n_rows=4, max_k=3):
    """One randomized batch: (msgs, sig_rows, pk_rows, row_keys).

    Rows cover empty committees, infinity (None) signature/pubkey
    slots, tampered signatures, and honest rows. Shapes stay inside one
    compile bucket (n_rows=4, width<=4) so the randomized rounds reuse
    one compiled program. Row keys are derived from the pk row CONTENT
    (member set + None pattern) — the caller contract that keys
    uniquely determine the row's points."""
    msgs, sig_rows, pk_rows, keys = [], [], [], []
    for _ in range(n_rows):
        kind = rng.random()
        tag = b"res-msg-%d" % rng.randrange(6)
        if kind < 0.15:
            msgs.append(tag)
            sig_rows.append([])
            pk_rows.append([])
            keys.append(None)
            continue
        k = rng.randrange(1, max_k + 1)
        members = rng.sample(range(len(KEYPOOL)), k)
        sigs = [bls.bls_sign(tag, KEYPOOL[i][0]) for i in members]
        pks = [KEYPOOL[i][1] for i in members]
        if kind < 0.3 and k >= 2:
            sigs[0] = None  # infinity signature slot (skipped, both paths)
        elif kind < 0.45 and k >= 2:
            pks[1] = None  # infinity pubkey slot
        elif kind < 0.6:
            sigs[-1] = bls.bls_sign(b"tampered", KEYPOOL[members[-1]][0])
        msgs.append(tag)
        sig_rows.append(sigs)
        pk_rows.append(pks)
        keys.append((tuple(members),
                     tuple(i for i, p in enumerate(pks) if p is None)))
    return msgs, sig_rows, pk_rows, keys


# the u16 wire compiles its own programs (tens of seconds cold on
# XLA:CPU): it runs in the full suite, the default wire in the fast tier
@pytest.mark.parametrize(
    "wire", ["i32", pytest.param("u16", marks=pytest.mark.slow)])
def test_randomized_resident_parity_and_eviction(monkeypatch, wire):
    """Randomized rounds under a ~2 KB device budget: sync and async
    resident verdicts match the scalar backend bit-for-bit while the
    LRU evicts under churn and the byte accounting stays bounded."""
    if wire == "u16":
        monkeypatch.setenv("GETHSHARDING_TPU_WIRE", "u16")
    else:
        monkeypatch.delenv("GETHSHARDING_TPU_WIRE", raising=False)
    monkeypatch.setenv("GETHSHARDING_TPU_RESIDENT", "1")
    monkeypatch.setenv("GETHSHARDING_TPU_RESIDENT_MB", "0.002")
    backend = JaxSigBackend()
    py = get_backend("python")
    evictions = metrics.counter("jax/pk_device_cache/evictions")
    before = evictions.value
    rng = random.Random(1234 if wire == "i32" else 4321)
    for _ in range(3):
        msgs, sig_rows, pk_rows, keys = _rand_round(rng)
        want = py.bls_verify_committees(msgs, sig_rows, pk_rows)
        sync = backend.bls_verify_committees(
            msgs, sig_rows, pk_rows, pk_row_keys=keys)
        future = backend.bls_verify_committees_async(
            msgs, sig_rows, pk_rows, pk_row_keys=keys)
        assert sync == future.result() == want
        assert future.done()
    # row-key churn under the tiny budget must have evicted, and the
    # accounted row bytes must respect it
    assert evictions.value > before
    assert backend._pk_dev_bytes <= backend._resident_budget


def test_warm_device_cache_ships_zero_g2_bytes():
    """The steady-state audit shape: identical keyed committees every
    dispatch. Cold ships the G2 planes; warm must ship ZERO G2 bytes
    (full device-cache hit) with an unchanged verdict — the acceptance
    ledger, here at a small scale."""
    backend = JaxSigBackend()  # fresh cache; defaults (resident on)
    assert backend._resident
    rng = random.Random(99)
    msgs, sig_rows, pk_rows, keys = _rand_round(rng)
    while not any(pk_rows):  # need at least one pointful row
        msgs, sig_rows, pk_rows, keys = _rand_round(rng)
    want = get_backend("python").bls_verify_committees(
        msgs, sig_rows, pk_rows)
    cold = backend.bls_verify_committees(
        msgs, sig_rows, pk_rows, pk_row_keys=keys)
    assert cold == want
    assert backend.last_wire["g2_wire_bytes"] > 0
    # the committee compile-cache key carries the wire dtype: flipping
    # GETHSHARDING_TPU_WIRE compiles a DIFFERENT program for the same
    # (bucket, width), which must count as a miss, not a hit (keyed
    # dispatches run the precomp op when GETHSHARDING_PRECOMP is on)
    assert any(k[0] in ("bls_committee", "bls_committee_precomp")
               and backend._wire in k[1:]
               for k in backend._shape_seen)
    warm = backend.bls_verify_committees(
        msgs, sig_rows, pk_rows, pk_row_keys=keys)
    assert warm == want
    assert backend.last_wire["g2_wire_bytes"] == 0
    assert (backend.last_wire["pk_hit_rows"]
            == backend.last_wire["pk_rows"]
            == sum(1 for r in pk_rows if r))
    assert backend.last_wire["pk_hit_bytes"] > 0
    # a SHORT key list (fewer keys than rows) marks the trailing rows
    # uncached instead of dropping them — the host row cache's contract,
    # kept by the resident path
    assert backend.bls_verify_committees(
        msgs, sig_rows, pk_rows, pk_row_keys=keys[:1]) == want
    # resident off: every dispatch re-ships the planes (the A/B the
    # bench reports), verdict still identical
    import os

    os.environ["GETHSHARDING_TPU_RESIDENT"] = "0"
    try:
        off = JaxSigBackend()
        assert off.bls_verify_committees(
            msgs, sig_rows, pk_rows, pk_row_keys=keys) == want
        assert off.last_wire["g2_wire_bytes"] > 0
    finally:
        del os.environ["GETHSHARDING_TPU_RESIDENT"]


def test_notary_overlapped_audit_matches_batched():
    """`audit_periods(..., overlap=True)` (the marshal/dispatch
    pipeline) must return exactly the batched single-dispatch form's
    per-period results, including the nothing-auditable period."""
    from gethsharding_tpu.actors.notary import Notary
    from gethsharding_tpu.core.shard import Shard
    from gethsharding_tpu.db.kv import MemoryKV
    from gethsharding_tpu.mainchain.client import SMCClient
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    notary = Notary(client=SMCClient(backend=SimulatedMainchain()),
                    shard=Shard(0, MemoryKV()),
                    sig_backend=get_backend("python"))
    rng = random.Random(7)
    rows_by_period = {3: None}  # period 3: nothing auditable
    for p in (1, 2):
        msgs, sig_rows, pk_rows, keys = _rand_round(rng, n_rows=3)
        rows_by_period[p] = {
            "shards": list(range(len(msgs))),
            "msgs": msgs, "sig_rows": sig_rows, "pk_rows": pk_rows,
            "pk_keys": keys,
            "signed_counts": [len(s) for s in sig_rows],
            "total_counts": [len(s) for s in sig_rows],
            "expected": [len(s) >= notary.config.quorum_size
                         for s in sig_rows],
        }
    notary._collect_audit_rows = lambda p: rows_by_period[p]

    batched = notary.audit_periods([1, 2, 3])
    mismatches_after_batched = notary.audit_mismatches
    overlapped = notary.audit_periods([1, 2, 3], overlap=True)
    assert overlapped == batched
    assert batched[3] is None
    # both passes judged the same rows the same way
    assert (notary.audit_mismatches - mismatches_after_batched
            == mismatches_after_batched)
    assert notary.audits_run == 4  # 2 auditable periods x 2 passes
