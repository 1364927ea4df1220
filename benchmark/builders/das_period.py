"""Request builder: whole periods of sampled data-availability rows for
`shard_dasVerify`.

Serves the configurations whose request is the notary's availability
vote of a whole period in `--da-mode sampled` (`actors/notary.py`
`_sampled_verdicts`): for each of `rows` shards the notary's `samples`
sampled (chunk, sibling path) rows, all shards in ONE
`das_verify_samples` call.

The data set is made from the seed through the protocol's own objects,
as `das/service.py` `DASService.publish` and `collect_rows` make it:
per (shard, period) a random body of `body_bytes` is erasure-extended
(`das.erasure.extend_body` at `parity`), every extended chunk's netstore
key is a leaf of the commitment tree (`das.proofs.chunk_leaf`,
`merkle_levels`), the notary's indices are
`das.sampler.sample_indices(sample_seed(account, shard, period,
das_root), samples, n)`, and a row is the sampled chunk, its index, its
`merkle_proof` and the DAS root. Only the sampled chunks and paths are
kept, never the bodies.

Then the period's `faults` are dealt, each to one row, in distinct
shards where there are enough: each must cost that row's verdict and
never an error. `periods` periods are built; request g serves period
`g % periods`.

`expected` is the construction's own answer; `check` holds every row of
it against the scalar reference (`PythonSigBackend`), which shares no
code with the device path or with `marshal_samples`.
"""

from __future__ import annotations

import itertools
import random

FIRST_PERIOD = 1


def _withheld(row):
    """The proposer serves other bytes for the chunk: a full-size chunk
    that does not hash to the committed leaf."""
    chunk = bytearray(row["chunk"])
    chunk[len(chunk) // 2] ^= 0x5A
    row["chunk"] = bytes(chunk)


def _broken_sibling(row):
    proof = list(row["proof"])
    level = len(proof) // 2
    proof[level] = bytes(b ^ 0xFF for b in proof[level])
    row["proof"] = tuple(proof)


def _short_chunk(row):
    row["chunk"] = row["chunk"][:-1]


def _index_outside(row):
    """An index beyond the tree the path proves."""
    row["index"] += 1 << len(row["proof"])


def _ragged_path(row):
    """A sibling of 31 bytes: a path that is no path."""
    proof = list(row["proof"])
    proof[-1] = proof[-1][:-1]
    row["proof"] = tuple(proof)


def _short_path(row):
    """The root's last sibling left off: a well-formed path of another
    tree."""
    row["proof"] = tuple(row["proof"][:-1])


FAULTS = {"withheld": _withheld, "broken_sibling": _broken_sibling,
          "short_chunk": _short_chunk, "index_outside": _index_outside,
          "ragged_path": _ragged_path, "short_path": _short_path}


def _shard_rows(task):
    """Pool worker: one (shard, period). Publishes a body as a proposer
    does and collects the notary's rows as `collect_rows` does: returns
    (das_root, n, [row...]), a row a dict of chunk, index and proof."""
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.das.erasure import extend_body
    from gethsharding_tpu.das.proofs import (chunk_leaf, merkle_levels,
                                             merkle_proof)
    from gethsharding_tpu.das.sampler import sample_indices, sample_seed

    seed, period, shard, body_bytes, parity, samples, account = task
    rng = random.Random(int.from_bytes(keccak256(
        b"benchmark-%d-das-body-%d-%d" % (seed, period, shard)), "big"))
    extended = extend_body(rng.randbytes(body_bytes), parity_ratio=parity)
    levels = merkle_levels([chunk_leaf(c) for c in extended.chunks])
    das_root = levels[-1][0]
    indices = sample_indices(sample_seed(account, shard, period, das_root),
                             samples, extended.n)
    return das_root, extended.n, [
        {"chunk": extended.chunks[i], "index": i,
         "proof": merkle_proof(levels, i)} for i in indices]


def deal_faults(config: dict, seed: int, period: int, per_shard: int) -> list:
    """Which row takes which fault: [(kind, shard, slot)], the shards
    distinct while there are enough of them, the rows always."""
    rows, kinds = config["rows"], config["faults"]
    rng = random.Random(seed * 1_000_003 + period)
    if len(kinds) > rows * per_shard:
        raise ValueError(f"{len(kinds)} faults for {rows * per_shard} rows")
    order = rng.sample(range(rows), rows)
    shards = [order[i % rows] for i in range(len(kinds))]
    slots = {shard: rng.sample(range(per_shard), per_shard)
             for shard in sorted(set(shards))}
    return [(kind, shard, slots[shard].pop())
            for kind, shard in zip(kinds, shards)]


def build(config: dict, seed: int, workers: int = 1) -> dict:
    """The data set of `config` for `seed`: plain lists, tuples and
    bytes only, so it pickles without the package's classes."""
    import multiprocessing

    from gethsharding_tpu.crypto.keccak import keccak256

    rows = config["rows"]
    account = keccak256(b"benchmark-%d-das-notary" % seed)[:20]
    numbers = range(FIRST_PERIOD, FIRST_PERIOD + config["periods"])
    tasks = [(seed, period, shard, config["body_bytes"], config["parity"],
              config["samples"], account)
             for period in numbers for shard in range(rows)]
    if workers > 1:
        # spawn, never fork: the workers import only the host-side DAS
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            shards = pool.map(_shard_rows, tasks, chunksize=1)
    else:
        shards = [_shard_rows(task) for task in tasks]

    periods = []
    for p, period in enumerate(numbers):
        mine = shards[p * rows:(p + 1) * rows]
        per_shard = len(mine[0][2])
        faults = deal_faults(config, seed, period, per_shard)
        for kind, shard, slot in faults:
            FAULTS[kind](mine[shard][2][slot])
        flat = [(root, row) for root, _, shard_rows in mine
                for row in shard_rows]
        faulty = {shard * per_shard + slot for _, shard, slot in faults}
        periods.append({
            "period": period,
            "chunks": [row["chunk"] for _, row in flat],
            "indices": [row["index"] for _, row in flat],
            "proofs": [row["proof"] for _, row in flat],
            "roots": [root for root, _ in flat],
            "expected": [i not in faulty for i in range(len(flat))],
            "faults": faults, "samples": per_shard,
            "chunks_per_body": mine[0][1],
        })
    return {"seed": seed, "account": account, "periods": periods}


def arguments(period: dict) -> tuple:
    """A period's `das_verify_samples` arguments."""
    return (period["chunks"], period["indices"], period["proofs"],
            period["roots"])


def shard_verdicts(period: dict, verdicts) -> list:
    """The notary's verdict per shard: available iff every one of its
    samples verified."""
    per = period["samples"]
    return [all(verdicts[s:s + per]) for s in range(0, len(verdicts), per)]


def check(config: dict, dataset: dict, seed: int) -> list:
    """The scalar reference on EVERY row of every period: returns
    [period, rows held] pairs after asserting that the reference agrees
    with the construction."""
    from gethsharding_tpu.sigbackend import PythonSigBackend

    reference, held = PythonSigBackend(), []
    for period in dataset["periods"]:
        got = reference.das_verify_samples(*arguments(period))
        if got != period["expected"]:
            wrong = [i for i, (g, w) in enumerate(zip(got,
                                                      period["expected"]))
                     if g != w]
            raise AssertionError(
                f"scalar reference disagrees with the construction in "
                f"period {period['period']} on rows {wrong}")
        held.append([period["period"], len(got)])
    return held


def requests(config: dict, dataset: dict, traffic: dict):
    """An endless iterator of (method, args, want, n_rows): request g is
    the whole of period `g % periods`. The op has no row keys, so the
    traffic's `row_keys` changes nothing here."""
    for g in itertools.count():
        period = dataset["periods"][g % len(dataset["periods"])]
        yield ("das_verify_samples", arguments(period), period["expected"],
               len(period["expected"]))
