"""Request builder: whole periods of BLS committee votes whose committees
are redrawn from a notary pool for every (shard, period).

Serves the configurations whose request is one `shard_verifyCommittees`
call of a whole period (`rows` shards) on a chain that samples each
shard's committee anew every period from a pool much larger than a
committee (`sharding_manager.sol:77-99`, `getNotaryInCommittee`), as
`committee_rows` serves those whose one committee signs every row.

The data set is made from the seed through the protocol's own objects:
`pool` notaries registered on a `SimulatedMainchain` with derived BLS
keys and proofs of possession (derived and proved in the signing pool,
registered here); per period and shard a committee of `committee`
DISTINCT pool indices drawn from a hash of (seed, period, shard), where
the prototype contract lets each sender test its own slot; one collation
root and vote digest per (shard, period); every vote signed with the
voter's registered key. Attendance is dealt, per period, from the same
fixed list over quorum..committee as `committee_rows` deals it, row 0 of
each period full, one row of each period carrying a forged vote and one
left empty. `periods` periods are signed; request g serves period
`g % periods`.

The row keys are what makes a replayed period new to the program: with
`period_keys` "fresh" request g sends `("benchmark", seed, g) + the
row's voter indices`, so no key is ever sent twice and a cache keyed on
row keys misses every row of every request, as it does on a live chain
where a committee never recurs. "period" sends the period's number in
g's place: the repeated-committee shape, for the tests.

`expected` is the construction's own answer; `check` holds it against the
scalar reference (`PythonSigBackend`), which shares no code with the
device path.
"""

from __future__ import annotations

import itertools
import random

from builders import committee_rows

FIRST_PERIOD = 1


def _derive(seed_bytes):
    """Pool worker: one notary. seed -> (address, BLS secret key, BLS
    public key, proof of possession)."""
    from gethsharding_tpu.mainchain.accounts import AccountManager

    manager = AccountManager()
    acct = manager.new_account(seed=seed_bytes)
    sk, pk = acct.bls_keypair()
    return acct.address, sk, pk, manager.bls_proof_of_possession(acct.address)


def draw(config: dict, seed: int) -> list:
    """Who sits and who votes, per period: no key is touched, so the
    draw at the full size costs milliseconds. Returns one dict a period:
    `rosters` (per row the committee's `committee` distinct pool
    indices, in slot order), `voters` (per row the sorted pool indices
    of those who voted), `forged_row`, `empty_row`."""
    from gethsharding_tpu.crypto.keccak import keccak256

    rows, committee, quorum, pool = (config[k] for k in (
        "rows", "committee", "quorum", "pool"))
    if pool < committee:
        raise ValueError(f"pool {pool} is smaller than a committee "
                         f"of {committee}")
    out = []
    for period in range(FIRST_PERIOD, FIRST_PERIOD + config["periods"]):
        rng = random.Random(seed * 1_000_003 + period)
        # row 0 keeps full attendance (so a period pads to the
        # committee's width); the forged and the empty row are drawn
        # among the others
        special = rng.sample(range(1, rows), min(2, rows - 1))
        forged_row = special[0]
        empty_row = special[1] if len(special) > 1 else None
        dealt = committee_rows.attendances(
            rows - 1 - (empty_row is not None), committee, quorum, full=False)
        rng.shuffle(dealt)
        rosters, voters = [], []
        for shard in range(rows):
            sampler = random.Random(int.from_bytes(keccak256(
                b"benchmark-%d-committee-%d-%d" % (seed, period, shard)),
                "big"))
            roster = sampler.sample(range(pool), committee)
            rosters.append(roster)
            if shard == empty_row:
                voters.append([])
            else:
                attend = committee if shard == 0 else dealt.pop()
                voters.append(sorted(sampler.sample(roster, attend)))
        out.append({"period": period, "rosters": rosters, "voters": voters,
                    "forged_row": forged_row, "empty_row": empty_row})
    return out


def build(config: dict, seed: int, workers: int = 1) -> dict:
    """The data set of `config` for `seed`."""
    import multiprocessing

    from gethsharding_tpu.crypto import bn256
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.params import ETHER, Config
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.smc.state_machine import vote_digest
    from gethsharding_tpu.utils.hexbytes import Hash32

    rows = config["rows"]
    periods = draw(config, seed)
    for period in periods:
        period["messages"] = [
            bytes(vote_digest(s, period["period"], Hash32(keccak256(
                b"benchmark-%d-root-%d-%d" % (seed, period["period"], s)))))
            for s in range(rows)]
    notary_seeds = [b"benchmark-%d-pool-notary-%d" % (seed, i)
                    for i in range(config["pool"])]

    def signing_tasks(sks):
        return [(period["messages"][s], [sks[i] for i in period["voters"][s]])
                for period in periods for s in range(rows)]

    if workers > 1:
        # spawn, never fork: the workers import only the scalar crypto
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            notaries = pool.map(_derive, notary_seeds, chunksize=8)
            sig_rows = pool.map(committee_rows._sign_row,
                                signing_tasks([n[1] for n in notaries]),
                                chunksize=1)
    else:
        notaries = [_derive(s) for s in notary_seeds]
        sig_rows = [committee_rows._sign_row(task) for task in
                    signing_tasks([n[1] for n in notaries])]

    chain = SimulatedMainchain(config=Config(
        shard_count=max(rows, 1), committee_size=config["committee"],
        quorum_size=config["quorum"]))
    for address, _, pk, pop in notaries:
        chain.fund(address, 2000 * ETHER)
        chain.register_notary(address, bls_pubkey=pk, bls_pop=pop)
    # a pool index is the contract's: the slot in `notary_pool`
    registry = chain.smc.notary_registry
    pubkeys = [registry[address].bls_pubkey
               for address in chain.smc.notary_pool]

    for p, period in enumerate(periods):
        period["sig_rows"] = sig_rows[p * rows:(p + 1) * rows]
        forged = period["forged_row"]
        # the forged vote: a registered voter's real signature over
        # ANOTHER row's digest, a well-formed G1 point that does not
        # verify here
        period["sig_rows"][forged][0] = bn256.bls_sign(
            period["messages"][(forged + 1) % rows],
            notaries[period["voters"][forged][0]][1])
        period["pk_rows"] = [[pubkeys[i] for i in row]
                             for row in period["voters"]]
        period["expected"] = [s not in (forged, period["empty_row"])
                              for s in range(rows)]
    return {"seed": seed, "periods": periods}


def check(config: dict, dataset: dict, seed: int) -> list:
    """The scalar reference, per period, as `committee_rows.check` holds
    it: on the rows False by construction plus a seeded sample of
    `scalar_sample_rows`. Returns the checked [period, row] pairs."""
    return [[period["period"], row] for period in dataset["periods"]
            for row in committee_rows.check(config, period,
                                            seed + period["period"])]


def row_keys(config: dict, dataset: dict, g: int) -> list:
    """Request g's row keys: the ordered voter indices determine a
    row's pubkeys (the notary's int-tuple idiom), and what stands
    before them says whether the program has met the row before."""
    period = dataset["periods"][g % len(dataset["periods"])]
    if config["period_keys"] == "fresh":
        stamp = g
    elif config["period_keys"] == "period":
        stamp = period["period"]
    else:
        raise ValueError(f"period_keys {config['period_keys']!r}: want "
                         f"'fresh' or 'period'")
    return [("benchmark", dataset["seed"], stamp) + tuple(row)
            for row in period["voters"]]


def requests(config: dict, dataset: dict, traffic: dict):
    """An endless iterator of (method, args, want, n_sigs): request g is
    the whole of period `g % periods`, with its row keys where the
    traffic sends them."""
    for g in itertools.count():
        period = dataset["periods"][g % len(dataset["periods"])]
        keys = row_keys(config, dataset, g) if traffic["row_keys"] else None
        yield ("bls_verify_committees",
               (period["messages"], period["sig_rows"], period["pk_rows"],
                keys),
               period["expected"],
               sum(len(r) for r in period["sig_rows"]))
