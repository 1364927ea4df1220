"""Request builder: whole periods of polynomial-multiproof availability
rows for `shard_dasPolyVerify`.

Serves the configurations whose request is the notary's availability
vote of a whole period in `--da-mode sampled --da-proofs poly`
(`actors/notary.py` `_poly_verdicts`): for each of `rows` shards ONE
row, the shard's 64-byte G1 commitment, the notary's `samples` sampled
indices, their chunk evaluations and ONE 64-byte G1 multiproof, all
shards in ONE `das_verify_multiproofs` call.

The data set is made from the seed through the protocol's own objects,
as `das/service.py` `DASService.publish` and `collect_poly_row` make
it: per (shard, period) the random body of `das_period.py` (the same
seed gives the same bodies) is erasure-extended
(`das.erasure.extend_body` at `parity`), each extended chunk's field
element is `pcs.chunk_value`, the commitment is `pcs.commit` of them,
the DAS root that seeds the draw is the commitment tree's
(`das.proofs.chunk_leaf`, `merkle_levels`), the notary's indices are
`das.sampler.sample_indices(sample_seed(account, shard, period,
das_root), samples, n)` and the proof is `pcs.open_multi` over them.

Then the period's `faults` are dealt, one a shard, to distinct shards
(at most all shards but one): each must cost that row's verdict and
never an error. `periods` periods are built; request g serves period
`g % periods`.

`expected` is the construction's own answer; `check` holds every
False row and a seeded sample of the others against the scalar
reference (`PythonSigBackend`, `das/pcs.verify_multi` over
`crypto/bn256.py`), which shares no code with the device path.

The configuration is the device-side MSM's deployment, and a program
that sums the two MSMs of a row on the host cannot run it: importing
this module refuses such a program before the child starts
(`require_device_msm`).
"""

from __future__ import annotations

import itertools
import os
import random
import sys

FIRST_PERIOD = 1
# true rows a period that `check` holds beside every False row
CHECK_SAMPLE = 9


def require_device_msm() -> None:
    """Exit non-zero, at once, under a program whose multiproof marshal
    sums both MSMs of every row on the host: it has no `row_coeffs`,
    the host half of the device MSM (PR 39). Such a program spends
    about 30 s of pure Python a request, so a window holds 2 requests
    and their median is the host's noise (PR 39's first check read a
    spread of 1,405.83 ms against a bound of 1,390.41 on it)."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from gethsharding_tpu.das import poly_proofs

    if not hasattr(poly_proofs, "row_coeffs"):
        raise SystemExit(
            "das_poly_period: this program sums the multiproof MSMs on "
            "the host (no das.poly_proofs.row_coeffs); the configuration "
            "needs them on the device")


require_device_msm()


def _altered(row, slot):
    """One sampled chunk altered after the commitment: the notary's
    evaluation of the bytes it fetched no longer opens."""
    evals = list(row["evals"])
    evals[slot] = row["altered"][slot]
    row["evals"] = evals


def _foreign_proof(row, other):
    """The multiproof of another shard's row."""
    row["proof"] = other["proof"]


def _failed_fetch(row, _):
    """A fetch that failed, as `collect_poly_row` synthesizes it: an
    empty proof and zero evaluations."""
    row["proof"] = b""
    row["evals"] = [0] * len(row["indices"])


FAULTS = {"altered_chunk": _altered, "foreign_proof": _foreign_proof,
          "failed_fetch": _failed_fetch}


def _shard_row(task):
    """Pool worker: one (shard, period). Publishes a body as a proposer
    does in poly mode and collects the notary's row as
    `collect_poly_row` does."""
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.das import pcs
    from gethsharding_tpu.das.erasure import extend_body
    from gethsharding_tpu.das.proofs import chunk_leaf, merkle_levels
    from gethsharding_tpu.das.sampler import sample_indices, sample_seed

    seed, period, shard, body_bytes, parity, samples, account = task
    rng = random.Random(int.from_bytes(keccak256(
        b"benchmark-%d-das-body-%d-%d" % (seed, period, shard)), "big"))
    extended = extend_body(rng.randbytes(body_bytes), parity_ratio=parity)
    values = [pcs.chunk_value(c) for c in extended.chunks]
    das_root = merkle_levels([chunk_leaf(c) for c in extended.chunks])[-1][0]
    indices = sample_indices(sample_seed(account, shard, period, das_root),
                             samples, extended.n)
    proof, evals = pcs.open_multi(values, indices)
    altered = []
    for i in indices:
        chunk = bytearray(extended.chunks[i])
        chunk[len(chunk) // 2] ^= 0x5A
        altered.append(pcs.chunk_value(bytes(chunk)))
    return {"commitment": pcs.g1_to_bytes(pcs.commit(values)),
            "indices": list(indices), "evals": evals,
            "proof": pcs.g1_to_bytes(proof), "n": extended.n,
            "altered": altered}


def deal_faults(config: dict, seed: int, period: int) -> list:
    """[(kind, shard, slot, other)]: distinct shards, all but one at
    most; `slot` the altered sample, `other` the shard whose proof a
    foreign-proof row carries."""
    rows, kinds = config["rows"], config["faults"]
    rng = random.Random(seed * 1_000_003 + period)
    kinds = kinds[:max(0, rows - 1)]
    shards = rng.sample(range(rows), len(kinds))
    return [(kind, shard, rng.randrange(config["samples"]),
             rng.choice([s for s in range(rows) if s != shard]))
            for kind, shard in zip(kinds, shards)]


def build(config: dict, seed: int, workers: int = 1) -> dict:
    """The data set of `config` for `seed`: plain lists and bytes only,
    so it pickles without the package's classes."""
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
            made = pool.map(_shard_row, tasks, chunksize=1)
    else:
        made = [_shard_row(task) for task in tasks]

    periods = []
    for p, period in enumerate(numbers):
        mine = made[p * rows:(p + 1) * rows]
        honest = [dict(row) for row in mine]
        faults = deal_faults(config, seed, period)
        for kind, shard, slot, other in faults:
            FAULTS[kind](mine[shard],
                         honest[other] if kind == "foreign_proof" else slot)
        faulty = {shard for _, shard, _, _ in faults}
        periods.append({
            "period": period,
            "commitments": [row["commitment"] for row in mine],
            "indices": [row["indices"] for row in mine],
            "evals": [row["evals"] for row in mine],
            "proofs": [row["proof"] for row in mine],
            "ns": [row["n"] for row in mine],
            "expected": [s not in faulty for s in range(rows)],
            "faults": faults,
        })
    return {"seed": seed, "account": account, "periods": periods}


def arguments(period: dict) -> tuple:
    """A period's `das_verify_multiproofs` arguments."""
    return (period["commitments"], period["indices"], period["evals"],
            period["proofs"], period["ns"])


def _reference(task):
    """Pool worker: the scalar verdicts of some rows."""
    from gethsharding_tpu.sigbackend import PythonSigBackend

    return PythonSigBackend().das_verify_multiproofs(*task)


def check(config: dict, dataset: dict, seed: int) -> list:
    """The scalar reference on every False row and CHECK_SAMPLE seeded
    true rows of every period (about a second a row in pure Python, so
    on a pool where there are many): returns [period, rows held] pairs
    after asserting that the reference agrees with the construction."""
    import multiprocessing
    import os

    picks = []
    for period in dataset["periods"]:
        want = period["expected"]
        good = [r for r, ok in enumerate(want) if ok]
        rng = random.Random(seed * 7_919 + period["period"])
        held = sorted([r for r, ok in enumerate(want) if not ok]
                      + rng.sample(good, min(CHECK_SAMPLE, len(good))))
        picks.append((period, held))
    tasks = [tuple([col[r]] for col in arguments(period))
             for period, held in picks for r in held]
    workers = min(len(tasks), 12, os.cpu_count() or 1)
    if workers > 2:
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            got = pool.map(_reference, tasks, chunksize=1)
    else:
        got = [_reference(task) for task in tasks]
    out, at = [], 0
    for period, held in picks:
        for r in held:
            if got[at] != [period["expected"][r]]:
                raise AssertionError(
                    f"scalar reference disagrees with the construction in "
                    f"period {period['period']} on row {r}")
            at += 1
        out.append([period["period"], len(held)])
    return out


def requests(config: dict, dataset: dict, traffic: dict):
    """An endless iterator of (method, args, want, n_rows): request g is
    the whole of period `g % periods`. The op has no row keys, so the
    traffic's `row_keys` changes nothing here."""
    for g in itertools.count():
        period = dataset["periods"][g % len(dataset["periods"])]
        yield ("das_verify_multiproofs", arguments(period),
               period["expected"], len(period["expected"]))
