"""Request builder: rows of BLS committee votes for `shard_verifyCommittees`.

Serves every configuration whose request is `rows_per_request` rows of a
data set of `rows` headers, each voted on by members of one registered
committee: the 100-row SMC period (one request = the whole period) and
the one-row vote check (requests cycle through 16 headers).

The data set is made from the seed through the protocol's own objects, as
`chip_smoke.build_workload` makes it (copied, so that a later change to
the smoke cannot move the yardstick): `committee` notaries registered on
a `SimulatedMainchain` with derived BLS keys and proofs of possession,
one collation root and vote digest per row, every vote signed with the
voter's registered key, one row carrying a forged vote and (where the
configuration says so) one row left empty. One difference, so that every
seed does the same work: attendance is not drawn per row but dealt from
ONE fixed list spread evenly over quorum..committee, in an order made
from the seed. Every seed therefore signs and verifies the same number
of votes.

`expected` is the construction's own answer; `check` holds it against the
scalar reference (`PythonSigBackend`), which shares no code with the
device path.
"""

from __future__ import annotations

import itertools
import random

PERIOD = 1


def _sign_row(task):
    """Pool worker: one row's votes. (digest, [sk...]) -> [G1 sig...]."""
    from gethsharding_tpu.crypto import bn256

    digest, sks = task
    return [bn256.bls_sign(digest, sk) for sk in sks]


def attendances(rows: int, committee: int, quorum: int, full: bool) -> list:
    """The fixed list of per-row attendances, before the seed orders it:
    all `committee` when `full`, else evenly spread over quorum..committee
    (the contract fixes only the quorum)."""
    if full:
        return [committee] * rows
    span = committee - quorum + 1
    return [quorum + (i * span) // rows for i in range(rows)]


def build(config: dict, seed: int, workers: int = 1) -> dict:
    """The data set of `config` for `seed`: plain lists and tuples only,
    so it pickles without the package's classes."""
    import multiprocessing

    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.mainchain.accounts import AccountManager
    from gethsharding_tpu.params import ETHER, Config
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.smc.state_machine import vote_digest
    from gethsharding_tpu.utils.hexbytes import Hash32

    rows, committee, quorum = (config["rows"], config["committee"],
                               config["quorum"])
    rng = random.Random(seed)
    chain = SimulatedMainchain(config=Config(
        shard_count=max(rows, 1), committee_size=committee,
        quorum_size=quorum))
    manager = AccountManager()
    accounts = [manager.new_account(seed=b"benchmark-%d-notary-%d"
                                    % (seed, i)) for i in range(committee)]
    for acct in accounts:
        chain.fund(acct.address, 2000 * ETHER)
        chain.register_notary(
            acct.address, bls_pubkey=acct.bls_pubkey,
            bls_pop=manager.bls_proof_of_possession(acct.address))
    registry = chain.smc.notary_registry
    pubkeys = [registry[acct.address].bls_pubkey for acct in accounts]
    sks = [acct.bls_keypair()[0] for acct in accounts]

    # row 0 keeps full attendance (so a period pads to the committee's
    # width); the forged and the empty row are drawn among the others
    special = rng.sample(range(1, rows), min(2, rows - 1))
    forged_row = special[0]
    empty_row = (special[1] if config.get("empty_row") and len(special) > 1
                 else None)
    dealt = attendances(rows - 1 - (empty_row is not None), committee,
                        quorum, config["attendance"] == "full")
    rng.shuffle(dealt)
    digests, voters = [], []
    for s in range(rows):
        root = Hash32(keccak256(b"benchmark-%d-root-%d" % (seed, s)))
        digests.append(bytes(vote_digest(s, PERIOD, root)))
        if s == empty_row:
            voters.append([])
        else:
            attend = committee if s == 0 else dealt.pop()
            voters.append(sorted(rng.sample(range(committee), attend)))
    tasks = [(digests[s], [sks[i] for i in voters[s]]) for s in range(rows)]
    if workers > 1:
        # spawn, never fork: the workers import only the scalar crypto
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            sig_rows = pool.map(_sign_row, tasks, chunksize=1)
    else:
        sig_rows = [_sign_row(task) for task in tasks]
    # the forged vote: a registered voter's real signature over ANOTHER
    # row's digest, a well-formed G1 point that does not verify here
    sig_rows[forged_row][0] = manager.bls_sign(
        accounts[voters[forged_row][0]].address,
        digests[(forged_row + 1) % rows])
    return {
        "messages": digests,
        "sig_rows": sig_rows,
        "pk_rows": [[pubkeys[i] for i in row] for row in voters],
        # the cache key: the ordered voter indices determine the row's
        # pubkeys (the notary's int-tuple idiom)
        "pk_row_keys": [("benchmark", seed) + tuple(row) for row in voters],
        "expected": [s not in (forged_row, empty_row) for s in range(rows)],
        "forged_row": forged_row, "empty_row": empty_row,
    }


def check(config: dict, dataset: dict, seed: int) -> list:
    """The scalar reference on the rows False by construction plus a
    seeded sample of `scalar_sample_rows` (every row when that covers the
    set): returns the checked rows after asserting that the reference
    agrees with the construction."""
    from gethsharding_tpu.sigbackend import PythonSigBackend

    n = len(dataset["messages"])
    rows = {dataset["forged_row"], dataset["empty_row"]} - {None}
    pool = [i for i in range(n) if i not in rows]
    rng = random.Random(seed ^ 0x5CA1A5)
    rows.update(rng.sample(pool, min(config["scalar_sample_rows"],
                                     len(pool))))
    rows = sorted(rows)
    got = PythonSigBackend().bls_verify_committees(
        [dataset["messages"][i] for i in rows],
        [dataset["sig_rows"][i] for i in rows],
        [dataset["pk_rows"][i] for i in rows])
    want = [dataset["expected"][i] for i in rows]
    if got != want:
        raise AssertionError(
            f"scalar reference disagrees with the construction on rows "
            f"{rows}: {got} != {want}")
    return rows


def requests(config: dict, dataset: dict, traffic: dict):
    """An endless iterator of (method, args, want, n_sigs): consecutive
    slices of `rows_per_request` rows, cycling through the data set, with
    the rows' cache keys where the traffic sends them."""
    n, per = len(dataset["messages"]), config["rows_per_request"]
    for start in itertools.cycle(range(0, n - per + 1, per)):
        cut = slice(start, start + per)
        keys = dataset["pk_row_keys"][cut] if traffic["row_keys"] else None
        yield ("bls_verify_committees",
               (dataset["messages"][cut], dataset["sig_rows"][cut],
                dataset["pk_rows"][cut], keys),
               dataset["expected"][cut],
               sum(len(r) for r in dataset["sig_rows"][cut]))
