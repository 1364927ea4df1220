"""The resampled-pool period (ISSUE 28): `benchmark/builders/pool_periods`
and the program's keyed committee path under it, on the CPU.

Every dispatch here has the shapes `tests/test_sigbackend_precomp.py`
keeps warm (bucket 4, width 4, the i32 wire): a 4-row period of
3-seat committees drawn from a pool of 8. Counts and verdicts only: no
time measured here means anything.
"""

import os
import sys

import pytest

from gethsharding_tpu import devscope, metrics
from gethsharding_tpu.sigbackend import JaxSigBackend, get_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402

pool_periods = run.load_builder("pool_periods")

SEED = 2**31 + 28
TINY = {"rows": 4, "committee": 3, "quorum": 2, "pool": 8, "periods": 2,
        "rows_per_request": 4, "period_keys": "fresh",
        "scalar_sample_rows": 2}
KEYED = {"row_keys": True}
TABLE_BYTES = 52_800 + 1     # one line table (88, 3, 2, 25) int32 + its flag


def _count(name):
    return metrics.DEFAULT_REGISTRY.get(name).value


@pytest.fixture(scope="module")
def tiny():
    data = pool_periods.build(TINY, SEED)
    data["checked_rows"] = pool_periods.check(TINY, data, SEED)
    return data


@pytest.fixture(scope="module")
def scalar_verdicts(tiny):
    """`PythonSigBackend` on every row of both periods."""
    reference = get_backend("python")
    return [reference.bls_verify_committees(
        period["messages"], period["sig_rows"], period["pk_rows"])
        for period in tiny["periods"]]


def _first_requests(config, data, n):
    stream = pool_periods.requests(config, data, KEYED)
    return [next(stream) for _ in range(n)]


# == (a) fresh keys and an LRU that evicts ===================================


def test_fresh_keys_under_eviction_give_the_scalar_verdicts(
        monkeypatch, tiny, scalar_verdicts):
    # room for three tables: the second request's inserts evict the first's
    monkeypatch.setenv("GETHSHARDING_TPU_RESIDENT_MB",
                       str(3.5 * TABLE_BYTES / (1 << 20)))
    backend = JaxSigBackend()
    assert backend._precomp
    names = ("jax/pk_device_cache/misses", "jax/pk_device_cache/hits",
             "jax/pk_device_cache/evictions")
    start = [_count(n) for n in names]
    misses = 0
    for g, (method, args, want, n_sigs) in enumerate(
            _first_requests(TINY, tiny, 6)):
        period = tiny["periods"][g % 2]
        before = _count(names[0])
        got = getattr(backend, method)(*args)
        assert got == scalar_verdicts[g % 2] == want, f"request {g}"
        assert got[period["forged_row"]] is False
        assert got[period["empty_row"]] is False
        pointful = sum(1 for row in period["pk_rows"] if row)
        assert _count(names[0]) - before == pointful == 3
        assert backend.last_wire["precomp"] is True
        assert backend.last_wire["pk_hit_rows"] == 0
        assert backend.last_wire["g2_wire_bytes"] > 0
        assert n_sigs == sum(len(r) for r in period["sig_rows"])
        misses += pointful
    assert [_count(n) - s for n, s in zip(names[:2], start)] == [misses, 0]
    assert _count(names[2]) - start[2] >= 5 * 3 - 3
    assert backend._pk_dev_bytes <= backend._resident_budget


# == (b) the same data under keys that repeat ================================


def test_repeated_keys_hit_every_row_and_ship_no_g2_byte(tiny,
                                                          scalar_verdicts):
    config = dict(TINY, period_keys="period")
    backend = JaxSigBackend()
    requests = _first_requests(config, tiny, 4)
    assert requests[0][1][3] == requests[2][1][3]      # keys repeat
    for g in (0, 1):
        assert backend.bls_verify_committees(*requests[g][1]) \
            == scalar_verdicts[g]
        assert backend.last_wire["g2_wire_bytes"] > 0
    hits, g2 = (_count("jax/pk_device_cache/hits"),
                _count("jax/wire/g2_bytes"))
    for g in (2, 3):
        assert backend.bls_verify_committees(*requests[g][1]) \
            == scalar_verdicts[g % 2]
        wire = backend.last_wire
        assert wire["g2_wire_bytes"] == 0
        assert wire["pk_hit_rows"] == wire["pk_rows"] == 3
    assert _count("jax/pk_device_cache/hits") - hits == 6
    assert _count("jax/wire/g2_bytes") == g2


def test_the_g2_counter_counts_what_the_ledger_says_crossed(tiny):
    backend = JaxSigBackend()
    before = _count("jax/wire/g2_bytes")
    backend.bls_verify_committees(*_first_requests(TINY, tiny, 1)[0][1])
    wire = backend.last_wire
    assert _count("jax/wire/g2_bytes") - before == wire["g2_wire_bytes"]
    # three miss rows travel in a bucket of four, width 4, int32 limbs,
    # x and y planes and a mask: the padded row is counted, it crossed
    assert wire["g2_wire_bytes"] == 2 * (4 * 4 * 2 * 25 * 4) + 4 * 4


# == (c) the precompute is bucketed and counted ==============================


def test_miss_counts_share_a_bucket_and_each_bucket_is_a_counted_compile(
        monkeypatch, tiny, scalar_verdicts):
    """Four pointful rows (bucket 4, width 4) under keys chosen so that
    4, 1, 2 and 3 rows miss in turn."""
    first, second = tiny["periods"]
    take = [i for i in range(4) if first["pk_rows"][i]] + [0]
    periods = [first, first, first, second]
    rows = [(periods[k]["messages"][i], periods[k]["sig_rows"][i],
             periods[k]["pk_rows"][i]) for k, i in enumerate(take)]
    want = [scalar_verdicts[0][i] for i in take[:3]] \
        + [scalar_verdicts[1][0]]
    heard = []
    monkeypatch.setattr(devscope.COMPILES, "after_compile",
                        lambda op, shape: heard.append((op, shape)))
    backend = JaxSigBackend()

    def send(new, stamp):
        """`new` rows under keys never sent, the rest under the first
        request's; returns what the three counts rose by."""
        keys = [("c", stamp if r < new else 0, r) for r in range(4)]
        counts = [_count("jax/compile_cache/misses"),
                  _count("devscope/compile/count"),
                  _count("jax/pk_device_cache/misses")]
        assert backend.bls_verify_committees(
            *(list(col) for col in zip(*rows)), pk_row_keys=keys) == want
        return [_count(n) - c for n, c in zip(
            ("jax/compile_cache/misses", "devscope/compile/count",
             "jax/pk_device_cache/misses"), counts)]

    def precomputes():
        return [shape for op, shape in heard if op == "g2_line_precompute"]

    # the first request also compiles the committee kernel's own shape
    # and the stack program of its dispatch bucket (PR 34)
    assert send(4, 0) == [3, 3, 4]
    assert precomputes() == [(4, 4, "i32")]
    assert send(1, 1) == [1, 1, 1]
    assert send(2, 2) == [1, 1, 2]
    assert precomputes() == [(4, 4, "i32"), (1, 4, "i32"), (2, 4, "i32")]
    # three misses pad to the bucket of four: no new program
    assert send(3, 3) == [0, 0, 3]
    assert send(1, 4) == [0, 0, 1]
    # one stack program a DISPATCH bucket, whatever the miss count
    assert [shape for op, shape in heard
            if op == "line_table_stack"] == [(4,)]
    assert len(heard) == 5
    noted = {key[1:] for key in backend._shape_seen
             if key[0] == "g2_line_precompute"}
    assert noted == {(1, 4, "i32"), (2, 4, "i32"), (4, 4, "i32")}


# == (d) the builder =========================================================


def test_the_same_seed_gives_the_same_data_set(tiny):
    again = pool_periods.build(TINY, SEED)
    again["checked_rows"] = tiny["checked_rows"]
    assert again == tiny
    assert pool_periods.draw(TINY, SEED + 1) != pool_periods.draw(TINY, SEED)


def test_the_draw_at_the_deployments_size():
    config = run.read_json("configs", "smc_period_pool1024_100x135.json")
    assert (config["pool"], config["periods"], config["period_keys"]) \
        == (1024, 2, "fresh")
    periods = pool_periods.draw(config, SEED)
    assert [p["period"] for p in periods] == [1, 2]
    rosters = [tuple(sorted(r)) for p in periods for r in p["rosters"]]
    assert len(rosters) == 200 == len(set(rosters))
    votes = []
    for period in periods:
        special = {period["forged_row"], period["empty_row"]}
        assert len(special) == 2 and 0 not in special
        for shard, (roster, voters) in enumerate(zip(period["rosters"],
                                                     period["voters"])):
            assert len(roster) == 135 == len(set(roster))
            assert all(0 <= i < 1024 for i in roster)
            assert set(voters) <= set(roster) and voters == sorted(voters)
            if shard == period["empty_row"]:
                assert voters == []
            else:
                assert 90 <= len(voters) <= 135
            if shard == 0:
                assert len(voters) == 135
        votes.append(sum(len(v) for v in period["voters"]))
    # every seed and every period verifies the same number of votes
    assert votes[0] == votes[1]
    assert votes == [sum(len(v) for v in p["voters"])
                     for p in pool_periods.draw(config, SEED + 7)]
    # a notary sits on about 100 * 135 / 1024 = 13 committees a period
    seats = [0] * 1024
    for roster in periods[0]["rosters"]:
        for i in roster:
            seats[i] += 1
    assert 1 <= min(seats) and max(seats) <= 30


def test_expected_is_false_on_the_forged_and_the_empty_row_alone(
        tiny, scalar_verdicts):
    for period, verdicts in zip(tiny["periods"], scalar_verdicts):
        false_rows = {period["forged_row"], period["empty_row"]}
        assert period["expected"] == [i not in false_rows for i in range(4)]
        assert verdicts == period["expected"]
        assert period["sig_rows"][period["empty_row"]] == []
        for row, voters in zip(period["pk_rows"], period["voters"]):
            assert len(row) == len(voters)
            assert len(voters) == 0 or 2 <= len(voters) <= 3
    assert {tuple(pair) for pair in tiny["checked_rows"]} >= {
        (p["period"], p[row]) for p in tiny["periods"]
        for row in ("forged_row", "empty_row")}


@pytest.mark.parametrize("period_keys, shared", [("fresh", 0), ("period", 4)])
def test_consecutive_requests_of_one_period_share_no_fresh_key(
        tiny, period_keys, shared):
    config = dict(TINY, period_keys=period_keys)
    requests = _first_requests(config, tiny, 3)
    first, _, third = (r[1][3] for r in requests)
    assert requests[0][1][:3] == requests[2][1][:3]    # period 1 again
    assert len(set(first)) == 4
    assert len(set(first) & set(third)) == shared
    assert not set(first) & set(requests[1][1][3])
    keyless = next(pool_periods.requests(config, tiny, {"row_keys": False}))
    assert keyless[1][3] is None
    with pytest.raises(ValueError):
        pool_periods.row_keys(dict(TINY, period_keys="content"), tiny, 0)
